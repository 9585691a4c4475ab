"""Cell files at a size the CPU holds (Pallas kernels in interpret mode):
the granite family at d_model 64, LeNet-5 at batch 4."""
from __future__ import annotations

import math

GRANITE = {
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab_size": 97,
    "sigma_init": math.sqrt(0.5 / 64),
    "engine": {"slots": 4, "page_size": 4, "prefill_chunk": 16,
               "max_prompt": 40, "max_output": 12,
               "num_uncertainty_samples": 32},
}
SERVE_TRAFFIC = {
    "kind": "serve", "mode": "open", "rate_rps": 4.0,
    "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 40},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
}
BATCH_TRAFFIC = {"kind": "batch", "batch": 4, "pool_batches": 3}
