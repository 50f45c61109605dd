"""``mx.gluon.model_zoo.text`` — decoder families over token ids."""
from . import afmoe, glm4_moe_lite, kimi_linear, smallthinker
from .afmoe import *  # noqa: F401,F403
from .glm4_moe_lite import *  # noqa: F401,F403
from .kimi_linear import *  # noqa: F401,F403
from .smallthinker import *  # noqa: F401,F403

__all__ = afmoe.__all__ + glm4_moe_lite.__all__ + kimi_linear.__all__ \
    + smallthinker.__all__
