"""Symbol-level model definitions.

Reference: example/image-classification/symbols/ (lenet.py, resnet.py,
alexnet.py, vgg.py, mlp.py) — the canonical Module-API model zoo.
"""
from .lenet import get_lenet, get_mlp
from .resnet import get_resnet_symbol
from .lstm_lm import lstm_lm_symbol
from .ssd import get_ssd_symbol
from . import falcon_h1
from . import lfm2
from . import smallthinker

__all__ = ["get_lenet", "get_mlp", "get_resnet_symbol", "lstm_lm_symbol", "falcon_h1", "lfm2", "smallthinker"]
