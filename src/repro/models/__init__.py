"""Composable model zoo: dense/MoE/SSM/hybrid decoder LMs, an enc-dec
backbone, and a VLM backbone — all pure-functional JAX over param pytrees,
built to be scanned over layers and sharded by `repro.dist.sharding`."""

from .lm import (init_lm, init_lm_params, lm_forward, lm_loss, lm_prefill,
                 init_decode_cache, lm_decode_step)
from .encdec import init_encdec, encdec_forward, encdec_loss
