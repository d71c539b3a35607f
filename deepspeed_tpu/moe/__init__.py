from deepspeed_tpu.moe.sharded_moe import (MoE, StackedExperts, moe_capacity,
                                           moe_leaf_spec, sum_moe_losses,
                                           top_k_gating)

__all__ = ["MoE", "StackedExperts", "moe_capacity", "moe_leaf_spec",
           "sum_moe_losses", "top_k_gating", "dropless_moe"]
from deepspeed_tpu.moe.dropless import dropless_moe  # noqa: F401
