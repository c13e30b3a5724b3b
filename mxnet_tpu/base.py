"""Base utilities for mxnet_tpu.

TPU-native re-design of the reference's base layer.  Where the reference
routes every frontend call through a C ABI (`include/mxnet/c_api.h`,
`python/mxnet/base.py:102-111` ctypes CDLL), this framework is a native
Python/JAX stack: ops lower straight to XLA, so there is no ABI boundary to
marshal through.  What survives from that layer is the *contract*: typed,
range-checked, string-configurable parameters (the reference's
``dmlc::Parameter``), a central error type, and name registries.
"""
from __future__ import annotations

import threading
import warnings

import numpy as _np

__all__ = [
    "MXNetError", "ParamError", "string_types", "numeric_types",
    "AttrScope", "NameManager", "classproperty",
]

string_types = (str,)
numeric_types = (float, int, _np.generic)


class MXNetError(Exception):
    """Error raised by mxnet_tpu (mirrors the reference's MXNetError,
    src/c_api/c_api_error.cc — here exceptions propagate natively)."""


class ParamError(MXNetError):
    """Raised when an op/iterator parameter fails validation."""


# ---------------------------------------------------------------------------
# Typed parameter descriptors — the dmlc::Parameter equivalent.
# Every op and iterator declares its config as {name: Param}; values arriving
# as python objects or as strings (symbol JSON round-trips attrs as strings,
# matching the reference's string-configurable C API) are converted and
# validated by the same descriptor.
# ---------------------------------------------------------------------------

class _Required:
    def __repr__(self):
        return "<required>"


REQUIRED = _Required()


def _parse_bool(v):
    if isinstance(v, bool):
        return v
    if isinstance(v, (int, float)):
        return bool(v)
    s = str(v).strip().lower()
    if s in ("true", "1", "yes"):
        return True
    if s in ("false", "0", "no", "none"):
        return False
    raise ParamError("cannot interpret %r as bool" % (v,))


def _parse_tuple(v, elem=int):
    """Parse '(1, 2)' / '[1,2]' / 3 / (1,2) into a tuple of elem type."""
    if v is None:
        return None
    if isinstance(v, (int, float)):
        return (elem(v),)
    if isinstance(v, (tuple, list)):
        return tuple(elem(x) for x in v)
    s = str(v).strip()
    if s in ("None", "null", ""):
        return None
    s = s.strip("()[]")
    if not s:
        return ()
    return tuple(elem(x.strip().strip("LlUu")) for x in s.split(",") if x.strip())


class Param:
    """One typed op parameter (cf. dmlc::Parameter field declaration)."""

    def __init__(self, ptype, default=REQUIRED, choices=None, doc=""):
        self.ptype = ptype
        self.default = default
        self.choices = choices
        self.doc = doc

    @property
    def required(self):
        return self.default is REQUIRED

    def convert(self, value, name, op_name=""):
        try:
            if value is None and self.ptype in ("shape", "shape_or_none",
                                                "int_or_none", "float_or_none",
                                                "str_or_none"):
                return None
            if self.ptype is int:
                v = int(value) if not isinstance(value, str) \
                    else int(str(value).strip().strip("LlUu"))
            elif self.ptype is float:
                v = float(value)
            elif self.ptype is bool:
                v = _parse_bool(value)
            elif self.ptype is str:
                v = str(value)
            elif self.ptype == "shape" or self.ptype == "shape_or_none":
                v = _parse_tuple(value, int)
            elif self.ptype == "float_tuple":
                v = _parse_tuple(value, float)
            elif self.ptype == "int_or_none":
                s = str(value).strip()
                v = None if s in ("None", "null", "") else int(float(s))
            elif self.ptype == "float_or_none":
                s = str(value).strip()
                v = None if s in ("None", "null", "") else float(s)
            elif self.ptype == "str_or_none":
                s = str(value)
                v = None if s in ("None", "null") else s
            else:  # passthrough custom
                v = value
        except (TypeError, ValueError) as e:
            raise ParamError(
                "%s: parameter %s=%r invalid: %s" % (op_name, name, value, e))
        if self.choices is not None and v is not None and v not in self.choices:
            raise ParamError("%s: parameter %s=%r not in %s"
                             % (op_name, name, v, self.choices))
        return v


def normalize_attrs(params_schema, attrs, op_name=""):
    """Validate/convert an attr dict against a {name: Param} schema.

    Unknown keys starting with ``__`` (symbol meta attrs like __ctx_group__)
    are passed through; other unknown keys raise, mirroring dmlc::Parameter
    strictness.
    """
    out = {}
    for k, v in attrs.items():
        if k.startswith("__") or k.startswith("_"):
            out[k] = v
            continue
        if k not in params_schema:
            raise ParamError("%s: unknown parameter %r (known: %s)"
                             % (op_name, k, sorted(params_schema)))
        out[k] = params_schema[k].convert(v, k, op_name)
    for k, p in params_schema.items():
        if k not in out:
            if p.required:
                raise ParamError("%s: missing required parameter %r" % (op_name, k))
            out[k] = p.default
    return out


def attrs_to_strings(attrs):
    """Serialize attrs for symbol JSON (reference stores all attrs as str)."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, bool):
            out[k] = "true" if v else "false"
        elif v is None:
            out[k] = "None"
        else:
            out[k] = str(v)
    return out


# ---------------------------------------------------------------------------
# Naming + attribute scopes (python/mxnet/name.py, attribute.py equivalents)
# ---------------------------------------------------------------------------

class NameManager:
    """Automatic unique naming for symbols/blocks (python/mxnet/name.py)."""

    _current = threading.local()

    def __init__(self):
        self._counter = {}
        self._old = None

    def get(self, name, hint):
        if name:
            return name
        if hint not in self._counter:
            self._counter[hint] = 0
        name = "%s%d" % (hint, self._counter[hint])
        self._counter[hint] += 1
        return name

    def __enter__(self):
        self._old = getattr(NameManager._current, "value", None)
        NameManager._current.value = self
        return self

    def __exit__(self, *args):
        NameManager._current.value = self._old

    @staticmethod
    def current():
        v = getattr(NameManager._current, "value", None)
        if v is None:
            v = NameManager()
            NameManager._current.value = v
        return v


class Prefix(NameManager):
    """NameManager that prepends a prefix to all names."""

    def __init__(self, prefix):
        super().__init__()
        self._prefix = prefix

    def get(self, name, hint):
        name = super().get(name, hint)
        return self._prefix + name


class AttrScope:
    """Scope for symbol attributes (python/mxnet/attribute.py); used for
    ctx_group model-parallel annotations among others."""

    _current = threading.local()

    def __init__(self, **kwargs):
        self._attr = kwargs
        self._old = None

    def get(self, attr):
        out = dict(self._attr)
        if attr:
            out.update(attr)
        return out

    def __enter__(self):
        self._old = getattr(AttrScope._current, "value", None)
        merged = dict(self._old._attr) if self._old else {}
        merged.update(self._attr)
        self._attr = merged
        AttrScope._current.value = self
        return self

    def __exit__(self, *args):
        AttrScope._current.value = self._old

    @staticmethod
    def current():
        v = getattr(AttrScope._current, "value", None)
        if v is None:
            v = AttrScope()
            AttrScope._current.value = v
        return v


class classproperty:
    def __init__(self, f):
        self.f = f

    def __get__(self, obj, owner):
        return self.f(owner)


def deprecated(msg):
    def deco(fn):
        def wrapper(*a, **kw):
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*a, **kw)
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        return wrapper
    return deco


def named_program(fn, name):
    """``fn`` under the name ``name``, for ``jax.jit``: the compiled
    program is then ``jit_<name>``, which is what the device's trace
    calls each of its runs (the ``XLA Modules`` line of a TPU plane).
    A wrapper, and not ``fn`` renamed, so that a bound method (an
    exported program's ``call``) takes a name too; it keeps ``fn``'s
    signature for ``static_argnames`` and sets no ``__wrapped__``,
    which JAX would follow back to ``fn``'s own name.  The compiled
    code is the same; a dispatch never calls the wrapper."""
    import inspect

    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    try:
        program.__signature__ = inspect.signature(fn)
    except (TypeError, ValueError):
        pass
    return program
