"""What a kernel of the program has to move or compute at the least, from
shapes: the numerators of the ``<kernel>_roofline_pct`` metrics. Count only
what MUST be read, so that no share can pass 100%."""

from __future__ import annotations

from typing import Any, Dict


def latent_read_bytes(live_rows: float, config: Dict[str, Any],
                      itemsize: int = 2) -> float:
    """Bytes ``latent_attention_read`` must bring in for one decode step
    over ``live_rows`` cache positions held by the active sequences: each
    is one ``kv_lora_rank + qk_rope_head_dim`` wide row a layer (576 x 2 B
    = 1,152 B; the stored row's padding to 640 and the rest of a 128-row
    block are the kernel's own overhead and are not counted)."""
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return live_rows * width * itemsize * config["num_hidden_layers"]
