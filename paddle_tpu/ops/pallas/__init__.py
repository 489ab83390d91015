"""Pallas TPU fused kernels (SURVEY §2.6 porting list)."""

from .flash_attention import (  # noqa: F401
    flash_attention, flash_attention_fwd, flash_attention_with_lse,
)
from .fused import (  # noqa: F401
    fused_bias_act, fused_dropout_add, fused_softmax_mask, swiglu,
)
from .norms import (  # noqa: F401
    fused_bias_dropout_residual_layer_norm, layer_norm, rms_norm,
)
from .linear_ce import (  # noqa: F401
    linear_cross_entropy_pallas, tune_linear_ce,
)
from .rope import fused_rope, rope_cos_sin  # noqa: F401
