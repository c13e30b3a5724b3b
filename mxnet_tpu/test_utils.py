"""Testing utilities: tolerance asserts, numeric-gradient checking,
cross-context consistency, random data helpers.

Reference: python/mxnet/test_utils.py — `assert_almost_equal:467`
(dtype-aware rtol/atol), `check_numeric_gradient:789` (finite-difference
autograd validation — SURVEY §4 calls it *the* universal op test),
`check_symbolic_forward/backward`, `check_consistency:1203` (cross-device),
`default_context`, `rand_ndarray`.

TPU-native redesign: gradients come from jax.vjp (there is no per-op
hand-written backward to validate in isolation), so the numeric checker's
job here is to catch (a) custom_vjp ops whose hand gradient drifts from the
forward (loss heads, BlockGrad-style semantics are *excluded* by design),
(b) impls whose forward is silently non-differentiable (integer casts,
stop_gradients), and (c) symbol-graph plumbing that drops or misroutes
cotangents.  The direct-op checker (`check_op_gradient`) drives the
whole-registry sweep in tests/test_op_gradients.py; the symbol checker
(`check_numeric_gradient`) validates the executor path end-to-end.
"""
import contextlib

import numpy as np

from .base import MXNetError
from .context import Context, current_context, cpu
from . import ndarray as nd

_DTYPE_RTOL = {np.dtype(np.float16): 1e-2, np.dtype(np.float32): 1e-4,
               np.dtype(np.float64): 1e-6, "bfloat16": 1e-2}
_DTYPE_ATOL = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
               np.dtype(np.float64): 1e-5, "bfloat16": 1e-1}


def default_context():
    """Context tests run on (reference test_utils.py default_context)."""
    return current_context()


def set_default_context(ctx):
    Context._default_ctx.value = ctx


def _as_np(x):
    if isinstance(x, nd.NDArray):
        return x.asnumpy()
    return np.asarray(x)


def _dtype_tol(dtype, table):
    d = np.dtype(dtype) if str(dtype) != "bfloat16" else "bfloat16"
    return table.get(d, 1e-5)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Dtype-aware relative+absolute tolerance assert (ref :467)."""
    a, b = _as_np(a), _as_np(b)
    if rtol is None:
        rtol = max(_dtype_tol(a.dtype, _DTYPE_RTOL),
                   _dtype_tol(b.dtype, _DTYPE_RTOL))
    if atol is None:
        atol = max(_dtype_tol(a.dtype, _DTYPE_ATOL),
                   _dtype_tol(b.dtype, _DTYPE_ATOL))
    if a.shape != b.shape:
        raise AssertionError("shape mismatch: %s.shape=%s vs %s.shape=%s"
                             % (names[0], a.shape, names[1], b.shape))
    af, bf = a.astype(np.float64), b.astype(np.float64)
    with np.errstate(invalid="ignore"):
        ok = np.isclose(af, bf, rtol=rtol, atol=atol, equal_nan=equal_nan)
    if ok.all():
        return
    bad = ~ok
    idx = tuple(int(i[0]) for i in np.nonzero(bad))
    rel = np.abs(af - bf) / (np.abs(bf) + atol)
    raise AssertionError(
        "%s and %s differ at %d/%d positions (rtol=%g atol=%g); worst at "
        "%s: %r vs %r (max rel err %g)"
        % (names[0], names[1], int(bad.sum()), bad.size, rtol, atol, idx,
           af[idx], bf[idx], float(np.nanmax(rel[bad]))))


def few_ulp_tol(want):
    """``(rtol, atol)`` for float32 outputs of two DIFFERENT XLA programs
    that compute the same rows — the same graph at another batch extent
    or under another partition.  They may vectorize reductions and exp
    differently and round apart in the last places, so they compare
    within 64 ulp of the element or 4 ulp of the output's largest value
    (a softmax output inherits its logit's rounding scaled by the
    logit's magnitude: 29 ulp seen on outputs near 1e-15 under jax
    0.9).  One compiled program against itself stays bitwise; a row
    served from the wrong request is off by O(1)."""
    eps = float(np.finfo(np.float32).eps)
    return 64 * eps, 4 * eps * float(np.abs(_as_np(want)).max())


def almost_equal(a, b, rtol=None, atol=None):
    try:
        assert_almost_equal(a, b, rtol, atol)
        return True
    except AssertionError:
        return False


# ---------------------------------------------------------------------------
# random data helpers
# ---------------------------------------------------------------------------

def rand_shape_2d(dim0=10, dim1=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    """Random dense or sparse NDArray (ref rand_ndarray)."""
    dtype = dtype or np.float32
    arr = np.random.uniform(-1, 1, shape).astype(dtype)
    if stype == "default":
        return nd.array(arr, ctx=ctx)
    density = 0.2 if density is None else density
    keep = np.random.uniform(0, 1, shape) < density
    arr = arr * keep
    dense = nd.array(arr, ctx=ctx)
    from .ndarray import sparse as _sp
    return _sp.cast_storage(dense, stype)


# ---------------------------------------------------------------------------
# numeric gradient checking
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _x64():
    """Enable float64 inside the checker: central differences in f32 lose
    ~half the significand to cancellation; f64 makes the sweep tolerances
    meaningful."""
    import jax
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", old)


def _scalarize(f, proj):
    """Project outputs to one scalar with fixed coefficients so d(scalar)/dx
    is a single VJP pull-back with cotangent = proj."""
    def scalar_f(*xs):
        outs = f(*xs)
        if not isinstance(outs, (list, tuple)):
            outs = (outs,)
        tot = 0.0
        for o, p in zip(outs, proj):
            if p is not None:
                tot = tot + (o * p).sum()
        return tot
    return scalar_f


def check_op_gradient(op_name, attrs, inputs, wrt=None, eps=1e-5,
                      rtol=1e-3, atol=1e-5, training=False, key_seed=0,
                      visible_only=True):
    """Finite-difference vs jax.grad for one registered op.

    ``inputs``: list of numpy arrays (ints allowed for index operands).
    ``wrt``: indices of inputs to differentiate (default: all float inputs).
    Runs in float64.  Raises AssertionError on mismatch.
    """
    import jax
    import jax.numpy as jnp
    from .ops.registry import get_op

    op = get_op(op_name)
    a = op.normalize(attrs or {})
    with _x64():
        xs = [np.asarray(x, np.float64) if np.issubdtype(
            np.asarray(x).dtype, np.floating) else np.asarray(x)
            for x in inputs]
        if op.stochastic:
            xs = [np.asarray(
                jax.random.PRNGKey(key_seed), dtype=np.uint32)] + xs
            if wrt is not None:
                # caller's wrt indexes the *user-visible* inputs; shift past
                # the implicit leading PRNG-key operand
                wrt = [i + 1 for i in wrt]
        if wrt is None:
            wrt = [i for i, x in enumerate(xs)
                   if np.issubdtype(x.dtype, np.floating)]
        f = op.bound(a, training=training)
        outs = f(*[jnp.asarray(x) for x in xs])
        if not isinstance(outs, (list, tuple)):
            outs = (outs,)
        n_vis = op.num_visible_outputs if visible_only else len(outs)
        if callable(n_vis):
            n_vis = n_vis(a)
        rng = np.random.default_rng(0)
        proj = []
        for i, o in enumerate(outs):
            if i < n_vis and np.issubdtype(np.dtype(o.dtype), np.floating):
                proj.append(jnp.asarray(
                    rng.standard_normal(o.shape), o.dtype))
            else:
                proj.append(None)
        if all(p is None for p in proj):
            raise MXNetError("%s: no float outputs to differentiate"
                             % op_name)
        scalar_f = _scalarize(f, proj)
        grads = jax.grad(scalar_f, argnums=tuple(wrt))(
            *[jnp.asarray(x) for x in xs])
        # the differences below call it twice an element: one compiled
        # program, not the op's eager dispatches each time (a fused unit
        # in interpret mode took 490 s of its 212 elements that way)
        fd_f = jax.jit(scalar_f)
        for gi, i in enumerate(wrt):
            x0 = xs[i]
            num = np.zeros_like(x0, dtype=np.float64)
            flat = x0.reshape(-1)
            nflat = num.reshape(-1)
            for j in range(flat.size):
                h = eps * max(1.0, abs(flat[j]))
                orig = flat[j]
                flat[j] = orig + h
                fp = float(fd_f(*[jnp.asarray(x) for x in xs]))
                flat[j] = orig - h
                fm = float(fd_f(*[jnp.asarray(x) for x in xs]))
                flat[j] = orig
                nflat[j] = (fp - fm) / (2 * h)
            assert_almost_equal(np.asarray(grads[gi], np.float64), num,
                                rtol=rtol, atol=atol,
                                names=("vjp[%s:%d]" % (op_name, i),
                                       "numeric"))


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-4,
                           rtol=1e-2, atol=None, grad_nodes=None, ctx=None):
    """Finite-difference check through the *symbol executor* path (ref :789).

    ``location``: dict arg name -> numpy array (or list in argument order).
    Validates that Executor.backward's gradients match central differences
    of the summed forward outputs.
    """
    ctx = ctx or default_context()
    arg_names = sym.list_arguments()
    if isinstance(location, (list, tuple)):
        location = dict(zip(arg_names, location))
    location = {k: np.array(v, np.float32) for k, v in location.items()}
    aux_states = {k: np.array(v, np.float32)
                  for k, v in (aux_states or {}).items()}
    if grad_nodes is None:
        grad_nodes = [n for n in arg_names
                      if np.issubdtype(location[n].dtype, np.floating)]

    args = {k: nd.array(v, ctx=ctx) for k, v in location.items()}
    aux = {k: nd.array(v, ctx=ctx) for k, v in aux_states.items()}
    grad_req = {n: ("write" if n in grad_nodes else "null")
                for n in arg_names}
    exe = sym.bind(ctx, args=args, aux_states=aux or None,
                   grad_req=grad_req)
    outs = exe.forward(is_train=True)
    rng = np.random.default_rng(0)
    proj = [rng.standard_normal(o.shape).astype(np.float32) for o in outs]
    exe.backward(out_grads=[nd.array(p, ctx=ctx) for p in proj])
    analytic = {n: exe.grad_dict[n].asnumpy().astype(np.float64)
                for n in grad_nodes}

    def fwd_scalar():
        outs = exe.forward(is_train=True)
        return sum(float((o.asnumpy().astype(np.float64) * p).sum())
                   for o, p in zip(outs, proj))

    for n in grad_nodes:
        base = location[n]
        num = np.zeros(base.shape, dtype=np.float64).reshape(-1)
        flat = base.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            h = numeric_eps * max(1.0, abs(orig))
            flat[j] = orig + h
            exe.arg_dict[n][:] = nd.array(base, ctx=ctx)
            fp = fwd_scalar()
            flat[j] = orig - h
            exe.arg_dict[n][:] = nd.array(base, ctx=ctx)
            fm = fwd_scalar()
            flat[j] = orig
            exe.arg_dict[n][:] = nd.array(base, ctx=ctx)
            num[j] = (fp - fm) / (2 * h)
        assert_almost_equal(analytic[n], num.reshape(base.shape),
                            rtol=rtol, atol=atol if atol is not None
                            else 1e-3,
                            names=("symbolic[%s]" % n, "numeric"))


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=1e-5,
                           aux_states=None, ctx=None):
    """Forward outputs vs expected numpy arrays (ref check_symbolic_forward)."""
    ctx = ctx or default_context()
    arg_names = sym.list_arguments()
    if isinstance(location, (list, tuple)):
        location = dict(zip(arg_names, location))
    args = {k: nd.array(np.asarray(v), ctx=ctx)
            for k, v in location.items()}
    aux = {k: nd.array(np.asarray(v), ctx=ctx)
           for k, v in (aux_states or {}).items()}
    exe = sym.bind(ctx, args=args, aux_states=aux or None,
                   grad_req={n: "null" for n in arg_names})
    outs = exe.forward(is_train=False)
    if not isinstance(expected, (list, tuple)):
        expected = [expected]
    for o, e in zip(outs, expected):
        assert_almost_equal(o, e, rtol=rtol, atol=atol,
                            names=("forward", "expected"))
    return outs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-4,
                            atol=1e-5, aux_states=None, grad_req="write",
                            ctx=None):
    """Backward grads vs expected numpy arrays (ref check_symbolic_backward)."""
    ctx = ctx or default_context()
    arg_names = sym.list_arguments()
    if isinstance(location, (list, tuple)):
        location = dict(zip(arg_names, location))
    if isinstance(expected, (list, tuple)):
        expected = dict(zip(arg_names, expected))
    args = {k: nd.array(np.asarray(v), ctx=ctx)
            for k, v in location.items()}
    aux = {k: nd.array(np.asarray(v), ctx=ctx)
           for k, v in (aux_states or {}).items()}
    req = {n: (grad_req if n in expected else "null") for n in arg_names} \
        if isinstance(grad_req, str) else grad_req
    exe = sym.bind(ctx, args=args, aux_states=aux or None, grad_req=req)
    exe.forward(is_train=True)
    exe.backward(out_grads=[nd.array(np.asarray(g), ctx=ctx)
                            for g in out_grads])
    for n, e in expected.items():
        assert_almost_equal(exe.grad_dict[n], e, rtol=rtol, atol=atol,
                            names=("grad[%s]" % n, "expected"))


def check_consistency(sym, ctx_list, scale=1.0, rtol=1e-4, atol=1e-4):
    """Run forward+backward under each context config and cross-compare
    (ref check_consistency:1203 — there cpu-vs-gpu, here cpu-vs-tpu or
    dtype-vs-dtype).

    ``ctx_list``: list of dicts like {'ctx': mx.cpu(), 'data': (2,3), ...,
    'type_dict': {'data': np.float32}} — same contract as the reference.
    """
    assert len(ctx_list) > 1
    results = []
    rng = np.random.default_rng(0)
    arg_names = sym.list_arguments()
    shapes0 = {k: v for k, v in ctx_list[0].items()
               if k not in ("ctx", "type_dict")}
    base = {n: (rng.standard_normal(shapes0[n]) * scale).astype(np.float32)
            for n in arg_names if n in shapes0}
    for cfg in ctx_list:
        ctx = cfg["ctx"]
        tdict = cfg.get("type_dict", {})
        args = {n: nd.array(base[n].astype(tdict.get(n, np.float32)),
                            ctx=ctx, dtype=tdict.get(n, np.float32))
                for n in base}
        exe = sym.bind(ctx, args=args,
                       grad_req={n: ("write" if n in base else "null")
                                 for n in arg_names})
        outs = exe.forward(is_train=True)
        proj = [np.ones(o.shape, np.float32) for o in outs]
        exe.backward(out_grads=[nd.array(p, ctx=ctx) for p in proj])
        results.append((outs, {n: exe.grad_dict[n] for n in base}))
    ref_outs, ref_grads = results[0]
    for outs, grads in results[1:]:
        for o, r in zip(outs, ref_outs):
            assert_almost_equal(o, r, rtol=rtol, atol=atol,
                                names=("out", "out_ref"))
        for n in grads:
            assert_almost_equal(grads[n], ref_grads[n], rtol=rtol,
                                atol=atol, names=("grad", "grad_ref"))
    return results
