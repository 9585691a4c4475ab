"""The deepseek-moe-16b cell at a size the CPU holds (Pallas kernels in
interpret mode): d_model 64, one dense layer (FFN 96 wide) and two MoE
layers, each with a 16-wide top-6 router, 2 shared experts and 8 held
routed experts (global 4-11) of width 32."""
from __future__ import annotations

import math

DEEPSEEK = {
    "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 16, "d_ff": 32, "dense_d_ff": 96, "vocab_size": 97,
    "num_experts": 8, "routed_experts": 16, "expert_offset": 4, "top_k": 6,
    "num_shared_experts": 2, "sigma_init": math.sqrt(0.5 / 64),
    "engine": {"slots": 4, "page_size": 4, "prefill_chunk": 16,
               "max_prompt": 40, "max_output": 12,
               "num_uncertainty_samples": 32},
}
SERVE_TRAFFIC = {
    "kind": "serve", "mode": "open", "rate_rps": 4.0,
    "prompt": {"median": 16, "sigma": 0.6, "min": 4, "max": 40},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
}
