"""``mx.gluon.model_zoo.text`` — decoder families over token ids."""
from .afmoe import *  # noqa: F401,F403
from .afmoe import __all__  # noqa: F401
