from . import api  # noqa: F401  (triggers registry install)
from . import decode_block  # noqa: F401  (the serving layer bodies)
from . import fused_cross_entropy  # noqa: F401  (logits-free CE head)
from .registry import all_ops, get_op  # noqa: F401
