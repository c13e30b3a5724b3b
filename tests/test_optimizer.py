"""Optimizer tests — numpy-oracle comparisons per the reference's
tests/python/unittest/test_optimizer.py pattern (compare against a plain
numpy re-implementation for a few steps)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt


def _run_steps(optimizer, w0, grads):
    w = mx.nd.array(w0.copy())
    state = optimizer.create_state(0, w)
    for g in grads:
        optimizer.update(0, w, mx.nd.array(g), state)
    return w.asnumpy()


def test_sgd_matches_numpy():
    rng = np.random.RandomState(0)
    w0 = rng.randn(4, 3).astype(np.float32)
    grads = [rng.randn(4, 3).astype(np.float32) for _ in range(5)]
    lr, wd, mom = 0.1, 0.01, 0.9

    got = _run_steps(opt.create("sgd", learning_rate=lr, wd=wd, momentum=mom),
                     w0, grads)

    w = w0.copy()
    m = np.zeros_like(w)
    for g in grads:
        m = mom * m - lr * (g + wd * w)
        w = w + m
    np.testing.assert_allclose(got, w, rtol=1e-5, atol=1e-6)


def test_sgd_no_momentum_clip():
    rng = np.random.RandomState(1)
    w0 = rng.randn(10).astype(np.float32)
    grads = [10 * rng.randn(10).astype(np.float32) for _ in range(3)]
    lr, clip = 0.05, 0.5
    got = _run_steps(opt.create("sgd", learning_rate=lr, clip_gradient=clip),
                     w0, grads)
    w = w0.copy()
    for g in grads:
        w = w - lr * np.clip(g, -clip, clip)
    np.testing.assert_allclose(got, w, rtol=1e-5, atol=1e-6)


def test_adam_matches_numpy():
    rng = np.random.RandomState(2)
    w0 = rng.randn(6).astype(np.float32)
    grads = [rng.randn(6).astype(np.float32) for _ in range(4)]
    lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-8
    got = _run_steps(opt.create("adam", learning_rate=lr), w0, grads)
    w = w0.copy()
    m = np.zeros_like(w)
    v = np.zeros_like(w)
    for t, g in enumerate(grads, 1):
        lr_t = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        w = w - lr_t * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["rmsprop", "adagrad", "adadelta", "ftrl",
                                  "adamax", "nadam", "nag", "signum",
                                  "signsgd", "dcasgd", "sgld"])
def test_optimizers_reduce_quadratic_loss(name):
    """Every optimizer should descend on f(w) = 0.5*||w||^2 (grad = w)."""
    w = mx.nd.array(np.full(8, 5.0, dtype=np.float32))
    o = opt.create(name, learning_rate=0.05)
    state = o.create_state(0, w)
    start = float((w * w).sum().asscalar())
    for _ in range(20):
        grad = w.copy()
        o.update(0, w, grad, state)
    end = float((w * w).sum().asscalar())
    assert end < start, "%s did not descend: %f -> %f" % (name, start, end)


def test_multi_precision_sgd():
    rng = np.random.RandomState(3)
    w0 = rng.randn(4).astype(np.float16)
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9,
                   multi_precision=True)
    w = mx.nd.array(w0, dtype=np.float16)
    state = o.create_state(0, w)
    assert state[1].dtype == np.float32
    o.update(0, w, mx.nd.array(rng.randn(4).astype(np.float16), dtype=np.float16), state)
    assert w.dtype == np.float16


def test_lr_scheduler_factor():
    from mxnet_tpu.lr_scheduler import FactorScheduler, MultiFactorScheduler, \
        PolyScheduler
    s = FactorScheduler(step=10, factor=0.5)
    s.base_lr = 1.0
    assert s(1) == 1.0
    assert s(11) == 0.5
    assert s(21) == 0.25

    m = MultiFactorScheduler(step=[5, 15], factor=0.1)
    m.base_lr = 1.0
    assert m(next(iter([3]))) == 1.0
    assert abs(m(6) - 0.1) < 1e-12
    assert abs(m(16) - 0.01) < 1e-12

    p = PolyScheduler(max_update=100, base_lr=1.0, pwr=2)
    assert p(0) == 1.0
    assert abs(p(50) - 0.25) < 1e-12
    assert p(100) == 0.0


def test_updater_serialization():
    o = opt.create("adam", learning_rate=0.01)
    u = opt.get_updater(o)
    w = mx.nd.ones((3,))
    u(0, mx.nd.ones((3,)), w)
    blob = u.get_states()
    u2 = opt.get_updater(opt.create("adam", learning_rate=0.01))
    u2.set_states(blob)
    w2 = mx.nd.ones((3,))
    u2(0, mx.nd.ones((3,)), w2)


def test_lr_wd_mult():
    o = opt.create("sgd", learning_rate=1.0, wd=0.1,
                   param_idx2name={0: "fc_weight", 1: "fc_bias"})
    o.set_lr_mult({"fc_weight": 0.5})
    assert o._get_lr(0) == 0.5
    assert o._get_lr(1) == 1.0
    # bias wd defaults to 0 (reference set_wd_mult semantics)
    assert o._get_wd(1) == 0.0
    assert abs(o._get_wd(0) - 0.1) < 1e-12


# ---------------------------------------------------------------------------
# the multi-tensor SGD update (ISSUE 26): one program for a list of
# parameters, bit for bit the per-parameter path
# ---------------------------------------------------------------------------

_MT_NAMES = {0: "conv_weight", 1: "conv_bias", 2: "bn_gamma", 3: "bn_beta",
             4: "fc_weight"}
_MT_SHAPES = [(3, 3, 5, 7), (7,), (33,), (33,), (130, 17)]


def _mt_updater(**kw):
    """An SGD updater over the five toy parameters: wd with wd_mult by
    name (bias and beta at 0), an lr_mult on one of them."""
    kw = dict({"learning_rate": 0.1, "wd": 1e-2,
               "param_idx2name": _MT_NAMES}, **kw)
    o = opt.create("sgd", **kw)
    o.set_lr_mult({"fc_weight": 0.3})
    assert o._get_wd(1) == 0.0 and o._get_wd(3) == 0.0 and o._get_wd(0) > 0
    return opt.get_updater(o)


def _mt_arrays(seed):
    rng = np.random.RandomState(seed)
    return [mx.nd.array((3 * rng.randn(*s)).astype(np.float32))
            for s in _MT_SHAPES]


def _mt_steps(u, weights, steps, fused, seed=10):
    keys = list(range(len(weights)))
    for t in range(steps):
        grads = _mt_arrays(seed + t)
        if fused:
            assert u(keys, grads, weights) == 1
        else:
            for k in keys:
                assert u(k, grads[k], weights[k]) == 1


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(a.asnumpy(), b.asnumpy())


@pytest.mark.parametrize("rescale_grad", [1.0, 1.0 / 3])
@pytest.mark.parametrize("clip_gradient", [None, 0.7])
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_multi_tensor_sgd_is_the_per_parameter_path_bit_for_bit(
        momentum, clip_gradient, rescale_grad):
    kw = dict(momentum=momentum, clip_gradient=clip_gradient,
              rescale_grad=rescale_grad)
    one, many = _mt_updater(**kw), _mt_updater(**kw)
    w_one, w_many = _mt_arrays(0), _mt_arrays(0)
    _mt_steps(one, w_one, 1, fused=False)
    _mt_steps(many, w_many, 1, fused=True)
    held = [(w, many.states[k]) for k, w in enumerate(w_many)]
    _mt_steps(one, w_one, 2, fused=False, seed=20)
    _mt_steps(many, w_many, 2, fused=True, seed=20)
    for k in _MT_NAMES:
        assert _same(w_one[k], w_many[k]), _MT_NAMES[k]
        assert _same(one.states[k], many.states[k]), _MT_NAMES[k]
        # results land in the objects the caller and the updater hold
        assert w_many[k] is held[k][0] and many.states[k] is held[k][1]
        assert (many.states[k] is None) == (momentum == 0.0)
    assert many.optimizer.num_update == one.optimizer.num_update == 3
    assert many.optimizer._index_update_count \
        == one.optimizer._index_update_count == dict.fromkeys(_MT_NAMES, 3)


def test_multi_tensor_sgd_states_round_trip():
    """get_states() -> set_states() after fused steps: the next step of
    the restored updater is the next step of the one that went on."""
    u, w = _mt_updater(momentum=0.9), _mt_arrays(0)
    _mt_steps(u, w, 2, fused=True)
    u2 = _mt_updater(momentum=0.9)
    u2.set_states(u.get_states())
    w2 = [x.copy() for x in w]
    for k in _MT_NAMES:
        u2.optimizer._index_update_count[k] = 2
    u2.optimizer.num_update = 2
    _mt_steps(u, w, 1, fused=True, seed=30)
    _mt_steps(u2, w2, 1, fused=True, seed=30)
    for k in _MT_NAMES:
        assert _same(w[k], w2[k]) and _same(u.states[k], u2.states[k])


class _Compiles(object):
    """Compile requests as ``compile_requests_in_setup`` counts them
    (the persistent cache's request event, where a cache is set) and
    every backend compile: both off ``jax.monitoring``."""

    def __enter__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        self._on_event = lambda event, **kw: self._count(
            event == "/jax/compilation_cache/compile_requests_use_cache")
        self._on_duration = lambda event, _secs, **kw: self._count(
            event == dispatch.BACKEND_COMPILE_EVENT)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def _count(self, hit):
        self.n += bool(hit)

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)


def test_multi_tensor_sgd_follows_a_scheduler_without_a_compile():
    from mxnet_tpu.lr_scheduler import FactorScheduler
    one, many = (_mt_updater(momentum=0.9, lr_scheduler=FactorScheduler(
        step=1, factor=0.5)) for _ in range(2))   # a scheduler keeps state
    w_one, w_many = _mt_arrays(0), _mt_arrays(0)
    _mt_steps(many, w_many, 1, fused=True)
    before = [x.asnumpy() for x in w_many]
    with _Compiles() as c:
        _mt_steps(many, w_many, 2, fused=True, seed=11)
        after = [x.asnumpy() for x in w_many]
    assert c.n == 0, "a new learning rate compiled %d programs" % c.n
    with _Compiles() as c:                      # the counter does count
        _mt_steps(one, w_one, 3, fused=False)
    assert c.n >= 3 * len(_MT_NAMES)
    assert many.optimizer._get_lr(0) < 0.1 / 2  # the rate did move
    for k in _MT_NAMES:
        assert not np.array_equal(before[k], after[k])
        assert _same(w_one[k], w_many[k])       # and the step used it


def test_multi_tensor_sgd_falls_back_per_parameter():
    """Row-sparse gradients, float16 master copies and a subclass with
    an `update` of its own go through the loop: n programs, and what the
    single-key calls give."""
    from mxnet_tpu.ndarray.sparse import row_sparse_array

    def rsp_grads():
        g = _mt_arrays(5)
        dense = np.zeros(_MT_SHAPES[4], np.float32)
        dense[[2, 9]] = 1.5
        g[4] = row_sparse_array((dense[[2, 9]], np.array([2, 9])),
                                shape=_MT_SHAPES[4])
        return g
    keys = list(_MT_NAMES)
    one, many = _mt_updater(momentum=0.9), _mt_updater(momentum=0.9)
    w_one, w_many = _mt_arrays(0), _mt_arrays(0)
    assert many(keys, rsp_grads(), w_many) == len(keys)
    for k, g in zip(keys, rsp_grads()):
        one(k, g, w_one[k])
    assert all(_same(a, b) for a, b in zip(w_one, w_many))
    assert not np.array_equal(w_many[4].asnumpy()[2], _mt_arrays(0)[4].asnumpy()[2])
    assert np.array_equal(w_many[4].asnumpy()[3], _mt_arrays(0)[4].asnumpy()[3])

    mp = opt.get_updater(opt.create("sgd", learning_rate=0.1, momentum=0.9,
                                    multi_precision=True))
    w16 = [mx.nd.array(np.ones(4), dtype=np.float16) for _ in range(2)]
    g16 = [mx.nd.array(np.ones(4), dtype=np.float16) for _ in range(2)]
    assert mp([0, 1], g16, w16) == 2
    assert isinstance(mp.states[0], tuple) and w16[0].dtype == np.float16
    assert np.allclose(w16[0].asnumpy(), 0.9)

    class Halved(opt.SGD):
        def update(self, index, weight, grad, state):
            super().update(index, weight, grad * 0.5, state)
    u = opt.get_updater(Halved(learning_rate=1.0))
    w = [mx.nd.ones((3,)), mx.nd.ones((2,))]
    assert u([0, 1], [mx.nd.ones((3,)), mx.nd.ones((2,))], w) == 2
    assert np.allclose(w[0].asnumpy(), 0.5)


@pytest.mark.parametrize("narrow", [(0, 1, 2, 3, 4), (2,)])
def test_multi_tensor_sgd_leaves_float16_weights_to_the_loop(narrow):
    """The rates are float32 operands, exact for float32 arrays alone:
    a list with a float16 weight in it (no master copy) takes the loop,
    and reads what the single-key calls read."""
    def arrays(seed):
        return [mx.nd.array(a.asnumpy(), dtype=np.float16) if k in narrow
                else a for k, a in enumerate(_mt_arrays(seed))]
    keys = list(_MT_NAMES)
    one, many = _mt_updater(momentum=0.9), _mt_updater(momentum=0.9)
    w_one, w_many = arrays(0), arrays(0)
    for t in range(2):
        assert many(keys, arrays(40 + t), w_many) == len(keys)
        for k, g in zip(keys, arrays(40 + t)):
            one(k, g, w_one[k])
    for k in keys:
        assert w_many[k].dtype == (np.float16 if k in narrow
                                   else np.float32)
        assert _same(w_one[k], w_many[k]) \
            and _same(one.states[k], many.states[k])


@pytest.mark.parametrize("how", ["copyto_into_state", "copyto_from_state",
                                 "detach", "set_states"])
def test_arrays_sharing_a_buffer_with_a_state_outlive_the_update(how):
    """`copyto` on one device and `detach` share the buffer: whoever set
    a momentum from an array of theirs, or took one off the updater, still
    reads it after the next fused step, and the step is the one an updater
    with arrays of its own takes."""
    u, w = _mt_updater(momentum=0.9), _mt_arrays(0)
    ref, w_ref = _mt_updater(momentum=0.9), _mt_arrays(0)
    _mt_steps(u, w, 1, fused=True)
    _mt_steps(ref, w_ref, 1, fused=True)
    want = {k: v.asnumpy() for k, v in u.states.items()}
    if how == "copyto_into_state":
        held = {k: mx.nd.array(want[k]) for k in want}
        for k in want:
            held[k].copyto(u.states[k])
    elif how == "copyto_from_state":
        held = {k: u.states[k].copyto(mx.nd.zeros(want[k].shape))
                for k in want}
    elif how == "detach":
        held = {k: u.states[k].detach() for k in want}
    else:
        blob = u.get_states()
        held = dict(u.states)
        u.set_states(blob)
    _mt_steps(u, w, 2, fused=True, seed=50)
    _mt_steps(ref, w_ref, 2, fused=True, seed=50)
    for k in want:
        assert np.array_equal(held[k].asnumpy(), want[k]), _MT_NAMES[k]
        assert _same(u.states[k], ref.states[k]) and _same(w[k], w_ref[k])
        assert not np.array_equal(u.states[k].asnumpy(), want[k])


def test_multi_tensor_marks_and_default_rule():
    """`mark(index)` wraps each per-parameter update, `mark(None)` the
    one program; an optimizer without a rule loops in order."""
    import contextlib
    seen = []

    @contextlib.contextmanager
    def mark(index):
        seen.append(index)
        yield
    u = opt.get_updater(opt.create("adam", learning_rate=0.01))
    w = [mx.nd.ones((3,)), mx.nd.ones((2,))]
    assert u([4, 7], [mx.nd.ones((3,)), mx.nd.ones((2,))], w, mark) == 2
    assert seen == [4, 7] and sorted(u.states) == [4, 7]
    del seen[:]
    s = opt.get_updater(opt.create("sgd", learning_rate=0.01))
    assert s([4, 7], [mx.nd.ones((3,)), mx.nd.ones((2,))], w, mark) == 1
    assert seen == [None]


def test_import_builds_no_update_program():
    """Nothing of the multi-tensor path exists until the first list
    call: the decode cells import this package and pay nothing for it."""
    import subprocess
    import sys
    code = ("import mxnet_tpu.optimizer as o; "
            "assert o._multi_sgd_jit.cache_info().currsize == 0; "
            "print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.stdout.strip() == "ok", out.stderr[-2000:]
