"""gluon.model_zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import vision  # noqa: F401
from . import bert  # noqa: F401
from . import gpt  # noqa: F401
from . import afmoe  # noqa: F401
from . import keye  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import glm4_moe_lite  # noqa: F401
from . import ouro  # noqa: F401
from . import model_store  # noqa: F401
