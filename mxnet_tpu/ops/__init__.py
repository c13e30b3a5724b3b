"""Operator registry + implementations (the src/operator/ equivalent).

Importing this package registers all ops.  Frontends (`mxnet_tpu.ndarray`,
`mxnet_tpu.symbol`) generate their user-facing functions from this registry —
the same single-source-of-truth layout as the reference's NNVM registry
shared by GraphExecutor and Imperative (SURVEY §1).
"""
from .registry import (P, OpDef, register, register_opdef, get_op, list_ops,
                       alias_map, invoke_jax)

from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import shape_ops     # noqa: F401
from . import nn            # noqa: F401
from . import linalg        # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn           # noqa: F401
from . import contrib_det   # noqa: F401
from . import contrib_misc  # noqa: F401
from . import contrib_rcnn  # noqa: F401
from . import contrib_deform  # noqa: F401
from . import sparse_ops    # noqa: F401
from . import fused_unit    # noqa: F401
from . import cache         # noqa: F401
from . import transformer   # noqa: F401
