"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet ~0.12 (reference at /root/reference), built on JAX/XLA/Pallas.

Layer map (SURVEY §7): engine+storage collapse into XLA's async runtime;
ops are a single registry of pure-JAX impls; imperative NDArray+autograd ride
jax.vjp; Gluon hybridize / symbolic executors compile whole graphs with
jax.jit over sharded meshes; KVStore modes are mesh collectives.
"""
__version__ = "0.12.0.tpu1"

from .base import MXNetError
from . import config
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import base
from . import context
from . import random
from . import autograd
from . import ops
from . import operator  # registers the Custom op before namespaces build
ops.BUILTIN_OPS = frozenset(ops.registry._REGISTRY)  # pre-runtime snapshot
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from .executor import Executor
from . import cached_op
from .cached_op import CachedOp

ndarray.CachedOp = CachedOp
nd.CachedOp = CachedOp

from . import lr_scheduler
from . import optimizer
from . import optimizer as opt
from . import initializer
from . import initializer as init
from . import metric
from . import io
from . import recordio
from . import kvstore as kv
from . import kvstore
from . import model
from . import callback
from . import module
from . import profiler
from . import telemetry
from . import monitor
from .monitor import Monitor
from . import rnn
from . import rtc
from . import analysis
from . import predict
from .predict import Predictor
from . import serving
from . import visualization
from . import visualization as viz
from . import test_utils
from . import module as mod
from .module import Module
from . import gluon
