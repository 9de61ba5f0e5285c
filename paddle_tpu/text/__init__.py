"""paddle_tpu.text — NLP models & datasets (reference: python/paddle/text/)."""
from . import models  # noqa: F401
from . import datasets  # noqa: F401
from . import block_diffusion  # noqa: F401
from .datasets import (  # noqa: F401
    Conll05st, Imdb, Imikolov, Movielens, UCIHousing, WMT14, WMT16)
