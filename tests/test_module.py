"""Module API tests, incl. the end-to-end training slice (SURVEY §7 stage 4;
reference tests/python/unittest/test_module.py + tests/python/train/)."""
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio


def _mlp_symbol(num_hidden=32, num_classes=4):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    act1 = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act1, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _toy_classification(n=256, d=16, k=4, seed=0):
    """Linearly separable-ish blobs."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d) * 3
    X = np.zeros((n, d), dtype=np.float32)
    y = np.zeros(n, dtype=np.float32)
    for i in range(n):
        c = i % k
        X[i] = centers[c] + rng.randn(d) * 0.5
        y[i] = c
    return X, y


def test_module_bind_and_forward():
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Uniform(0.1))
    batch = mio.DataBatch(data=[mx.nd.ones((8, 16))],
                          label=[mx.nd.zeros((8,))])
    mod.forward(batch, is_train=False)
    outs = mod.get_outputs()
    assert outs[0].shape == (8, 4)
    p = outs[0].asnumpy()
    np.testing.assert_allclose(p.sum(axis=1), np.ones(8), rtol=1e-5)


def test_module_context_list_needs_a_sharding_plan():
    """One executor binds on one device: a context list without a plan
    must say so, not train on the first context alone; with a plan over
    the same devices the batch is sharded across them."""
    from mxnet_tpu.parallel.mesh import ShardingPlan, make_mesh
    ctxs = [mx.cpu(0), mx.cpu(1)]
    shapes = dict(data_shapes=[("data", (8, 16))],
                  label_shapes=[("softmax_label", (8,))])
    mod = mx.mod.Module(_mlp_symbol(), context=ctxs)
    with pytest.raises(mx.MXNetError, match="set_sharding_plan"):
        mod.bind(**shapes)
    assert not mod.binded
    mod.set_sharding_plan(ShardingPlan(
        make_mesh({"dp": 2}, devices=[c.jax_device() for c in ctxs]),
        batch_axis="dp"))
    mod.bind(**shapes)
    assert len(mod._exec.arg_dict["data"]._data.devices()) == 2


def test_gpu_context_raises_without_an_accelerator():
    """mx.gpu(i) names the i-th accelerator; on a CPU-only process it
    raises like mx.tpu(i) instead of resolving to a host device."""
    for ctx in (mx.gpu(0), mx.tpu(0)):
        with pytest.raises(RuntimeError):
            ctx.jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"


def test_module_fit_converges():
    """End-to-end convergence: the reference's tests/python/train pattern."""
    X, y = _toy_classification()
    train = mio.NDArrayIter(X, y, batch_size=32, shuffle=True)
    val = mio.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            num_epoch=10, eval_metric="acc",
            initializer=mx.init.Xavier())
    score = mod.score(val, "acc")
    assert score[0][1] > 0.9, "did not converge: %s" % score


def test_module_input_grads():
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 16))],
             label_shapes=[("softmax_label", (4,))],
             inputs_need_grad=True)
    mod.init_params()
    batch = mio.DataBatch(data=[mx.nd.ones((4, 16))],
                          label=[mx.nd.array([0, 1, 2, 3])])
    mod.forward(batch, is_train=True)
    mod.backward()
    igrads = mod.get_input_grads()
    assert igrads[0].shape == (4, 16)
    assert np.abs(igrads[0].asnumpy()).sum() > 0


def test_module_checkpoint_roundtrip(tmp_path):
    prefix = str(tmp_path / "model")
    X, y = _toy_classification(n=64)
    train = mio.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.fit(train, num_epoch=2, optimizer_params={"learning_rate": 0.1},
            epoch_end_callback=mx.callback.do_checkpoint(prefix))
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0002.params")

    mod2 = mx.mod.Module.load(prefix, 2, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (16, 16))],
              label_shapes=[("softmax_label", (16,))], for_training=False)
    # predictions must match
    batch = mio.DataBatch(data=[mx.nd.array(X[:16])], label=None)
    mod.forward(batch, is_train=False)
    out1 = mod.get_outputs()[0].asnumpy()
    mod2.forward(batch, is_train=False)
    out2 = mod2.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-6)


def test_module_predict_and_score():
    X, y = _toy_classification(n=64)
    it = mio.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    preds = mod.predict(it)
    assert preds.shape == (64, 4)
    res = mod.score(it, ["acc", "ce"])
    names = [n for n, v in res]
    assert "accuracy" in names and "cross-entropy" in names


def test_module_update_on_kvstore_matches_local():
    """kvstore-updater path must equal the local-updater path numerically."""
    X, y = _toy_classification(n=64, seed=1)

    def train_with(kvstore):
        np.random.seed(42)
        mx.random.seed(42)
        it = mio.NDArrayIter(X, y, batch_size=16)
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "rescale_grad": 1.0 / 16})
        for _ in range(3):
            it.reset()
            for batch in it:
                mod.forward_backward(batch)
                mod.update()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    p_none = train_with(None)
    p_kv = train_with(mx.kv.create("device"))
    for k in p_none:
        np.testing.assert_allclose(p_none[k], p_kv[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_bucketing_module():
    """Variable-length buckets share params (test_module.py pattern)."""
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.SoftmaxOutput(fc, name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(kvstore=None, optimizer="sgd")

    for key in [8, 8, 8]:
        batch = mio.DataBatch(
            data=[mx.nd.ones((4, key))], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[mio.DataDesc("data", (4, key))],
            provide_label=[mio.DataDesc("softmax_label", (4,))])
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    assert mod.get_outputs()[0].shape == (4, 4)


def test_sequential_module():
    net1 = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                 name="fc1")
    net2 = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("fc1_output"), num_hidden=4,
                              name="fc2"), name="softmax")
    mod1 = mx.mod.Module(net1, label_names=None, context=mx.cpu())
    mod2 = mx.mod.Module(net2, data_names=("fc1_output",), context=mx.cpu())
    seq = mx.mod.SequentialModule()
    seq.add(mod1).add(mod2, take_labels=True, auto_wiring=True)
    seq.bind(data_shapes=[("data", (4, 16))],
             label_shapes=[("softmax_label", (4,))])
    seq.init_params()
    seq.init_optimizer(kvstore=None)
    batch = mio.DataBatch(data=[mx.nd.ones((4, 16))],
                          label=[mx.nd.zeros((4,))])
    seq.forward(batch, is_train=True)
    seq.backward()
    seq.update()
    assert seq.get_outputs()[0].shape == (4, 4)


def test_python_loss_module_chain():
    """PythonLossModule supplies the head gradient for a symbol stage."""
    import numpy as np
    net1 = mx.sym.softmax(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc1"))
    mod1 = mx.mod.Module(net1, label_names=None, context=mx.cpu())
    loss = mx.mod.PythonLossModule(data_names=("softmax_output",))
    seq = mx.mod.SequentialModule()
    seq.add(mod1).add(loss, take_labels=True, auto_wiring=True)
    seq.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    seq.init_params()
    seq.init_optimizer(kvstore=None)
    X = np.random.RandomState(0).rand(4, 6).astype(np.float32)
    batch = mio.DataBatch(data=[mx.nd.array(X)],
                          label=[mx.nd.array(np.array([0., 1., 2., 3.]))])
    w0 = mod1.get_params()[0]["fc1_weight"].asnumpy().copy()
    for _ in range(5):
        seq.forward(batch, is_train=True)
        seq.backward()
        seq.update()
    w1 = mod1.get_params()[0]["fc1_weight"].asnumpy()
    assert np.abs(w1 - w0).sum() > 1e-3  # default softmax-CE grad flowed


def test_sequential_module_duplicate_param_rejected():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc_same")
    net_b = mx.sym.FullyConnected(mx.sym.Variable("fc_same_output"),
                                  num_hidden=4, name="fc_same")
    m1 = mx.mod.Module(net, label_names=None, context=mx.cpu())
    m2 = mx.mod.Module(net_b, data_names=("fc_same_output",),
                       label_names=None, context=mx.cpu())
    seq = mx.mod.SequentialModule().add(m1).add(m2, auto_wiring=True)
    seq.bind(data_shapes=[("data", (2, 4))])
    import pytest
    with pytest.raises(mx.MXNetError):
        seq.init_params()


def test_resnet_s2d_stem_exact_equivalence():
    """stem='s2d' is a pure reformulation: same conv0_weight shape, same
    outputs as the 7x7/s2 stem (models/resnet.py _s2d_stem)."""
    import numpy as np
    from mxnet_tpu.models import get_resnet_symbol
    rng = np.random.default_rng(0)
    B, H = 2, 64
    x = rng.standard_normal((B, H, H, 3)).astype(np.float32)
    outs = {}
    for stem in ("conv7", "s2d"):
        net = get_resnet_symbol(num_classes=10, num_layers=18,
                                image_shape=(3, H, H), layout="NHWC",
                                stem=stem)
        arg_shapes, _, aux_shapes = net.infer_shape(
            data=(B, H, H, 3), softmax_label=(B,))
        names = net.list_arguments()
        rng2 = np.random.default_rng(1)
        args = {n: mx.nd.array(
            rng2.standard_normal(s).astype(np.float32) * 0.1)
            for n, s in zip(names, arg_shapes)}
        args["data"] = mx.nd.array(x)
        aux = {n: mx.nd.array(np.zeros(s, np.float32) if "mean" in n
                              else np.ones(s, np.float32))
               for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
        assert dict(zip(names, arg_shapes))["conv0_weight"] == (64, 7, 7, 3)
        exe = net.bind(mx.cpu(), args=args, aux_states=aux,
                       grad_req={n: "null" for n in names})
        outs[stem] = exe.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(outs["conv7"], outs["s2d"], atol=2e-4)


# ---------------------------------------------------------------------------
# Module.update through the multi-tensor SGD rule (ISSUE 26)
# ---------------------------------------------------------------------------

class _Span(object):
    """Stands in for the open ``fit.optimizer`` span: keeps ``args`` and
    the names of the child marks."""

    def __init__(self):
        import contextlib
        self.args, self.marks = None, []
        self._null = contextlib.nullcontext()

    def child(self, name):
        self.marks.append(name)
        return self._null


def _per_parameter(monkeypatch):
    """SGD as it was before it had a multi-tensor rule: the loop."""
    from mxnet_tpu import optimizer as opt
    monkeypatch.setattr(opt.SGD, "update_multi", opt.Optimizer.update_multi)


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_fit_sgd_matches_the_per_parameter_path(monkeypatch, momentum):
    X, y = _toy_classification()

    def fit():
        np.random.seed(3)
        mx.random.seed(3)
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        mod.fit(mio.NDArrayIter(X, y, batch_size=32), optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "wd": 1e-3,
                                  "momentum": momentum},
                num_epoch=2, initializer=mx.init.Xavier())
        states = {k: None if v is None else v.asnumpy()
                  for k, v in mod._updater.states.items()}
        return ({k: v.asnumpy() for k, v in mod.get_params()[0].items()},
                states, mod)
    got, got_states, mod = fit()
    sp = _Span()
    mod._update_impl(sp)
    assert sp.args == {"updates": 1} and sp.marks == ["update/multi_tensor"]
    _per_parameter(monkeypatch)
    want, want_states, mod = fit()
    sp = _Span()
    mod._update_impl(sp)
    assert sp.args == {"updates": 4}
    assert sp.marks == ["update/" + n for n in mod._param_names]
    assert sorted(got) == sorted(want) and len(got) == 4
    for k in want:
        assert np.array_equal(got[k], want[k]), k
    for k in want_states:
        assert (want_states[k] is None) == (momentum == 0.0)
        assert np.array_equal(got_states[k], want_states[k]), k


def test_update_program_compiles_once():
    """Parameters come uncommitted from the initializer and momenta
    from `zeros`, and both come back committed: the first update commits
    them where the weight lives, so the second one compiles nothing."""
    from test_optimizer import _Compiles
    X, y = _toy_classification(n=32)
    # a width no other test of this process has compiled an update for
    mod = mx.mod.Module(_mlp_symbol(num_hidden=29), context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 16))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    assert not mod._exec.arg_dict["fc1_weight"]._data.committed
    batch = mio.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    counts = []
    for _ in range(3):
        mod.forward_backward(batch)
        with _Compiles() as c:
            mod.update()
        counts.append(c.n)
    assert counts[0] >= 1 and counts[1:] == [0, 0], counts


def _embedding_net():
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=40, output_dim=8,
                           sparse_grad=True, name="embed")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(emb), num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _half_net():
    w1 = mx.sym.Variable("fc1_weight", dtype=np.float16)
    b1 = mx.sym.Variable("fc1_bias", dtype=np.float16)
    fc1 = mx.sym.FullyConnected(mx.sym.Variable("data"), weight=w1, bias=b1,
                                num_hidden=8, name="fc1")
    fc2 = mx.sym.FullyConnected(mx.sym.Activation(fc1, act_type="relu"),
                                num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(mx.sym.Cast(fc2, dtype="float32"),
                                name="softmax")


@pytest.mark.parametrize("case", ["row_sparse", "multi_precision"])
def test_update_takes_the_loop_where_sgd_cannot_fuse(monkeypatch, case):
    """A row-sparse gradient, or float16 weights with float32 master
    copies: one program a parameter, with the lazy rows and the master
    copies the loop always gave."""
    rng = np.random.RandomState(0)
    if case == "row_sparse":
        net, dtype = _embedding_net(), np.float32
        data = rng.randint(0, 20, (8, 5)).astype(dtype)  # rows 20.. unseen
    else:
        net, dtype = _half_net(), np.float16
        data = rng.randn(8, 6).astype(dtype)
    batch = mio.DataBatch(data=[mx.nd.array(data, dtype=dtype)],
                          label=[mx.nd.array(np.arange(8) % 4)])

    def two_steps():
        np.random.seed(5)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[mio.DataDesc("data", data.shape, dtype)],
                 label_shapes=[mio.DataDesc("softmax_label", (8,))])
        mod.init_params(mx.init.Xavier())
        before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
        mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9, "wd": 1e-2,
            "multi_precision": case == "multi_precision"})
        for _ in range(2):
            mod.forward_backward(batch)
            sp = _Span()
            mod._update_impl(sp)
        return mod, sp, before
    mod, sp, before = two_steps()
    names = mod._param_names
    assert sp.args == {"updates": len(names)}
    assert sp.marks == ["update/" + n for n in names]
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    if case == "row_sparse":
        assert mod._exec.grad_dict["embed_weight"].stype == "row_sparse"
        # lazy update: rows no batch touched keep even their weight decay
        # (but the last: the gradient's padding rows carry index -1 and
        # `_rsp_sgd_update` takes that for row 39, as it did before)
        assert np.array_equal(after["embed_weight"][20:39],
                              before["embed_weight"][20:39])
        assert not np.array_equal(after["embed_weight"][:20],
                                  before["embed_weight"][:20])
    else:
        state = mod._updater.states[names.index("fc1_weight")]
        assert isinstance(state, tuple) and state[1].dtype == np.float32
        assert after["fc1_weight"].dtype == np.float16
        assert np.array_equal(after["fc1_weight"],
                              state[1].asnumpy().astype(np.float16))
    _per_parameter(monkeypatch)
    want = {k: v.asnumpy()
            for k, v in two_steps()[0].get_params()[0].items()}
    for k in want:
        assert not np.array_equal(after[k], before[k]), k
        assert np.array_equal(after[k], want[k]), k


def test_arrays_read_before_an_update_stay_readable():
    """The update program is given no buffer to keep: what
    `get_params()`, the arrays the caller initialised from (the
    executor's alias them), the executor's own handles and the momenta's
    held before a step is what they hold after it, and a `get_states()`
    taken before still loads."""
    X, y = _toy_classification(n=32)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 16))],
             label_shapes=[("softmax_label", (32,))])
    rng = np.random.RandomState(0)
    given = {n: mx.nd.array(rng.randn(*mod._exec.arg_dict[n].shape) * 0.1)
             for n in mod._param_names}
    given_np = {k: v.asnumpy() for k, v in given.items()}
    mod.init_params(arg_params=given, aux_params={})
    mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    batch = mio.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    mod.forward_backward(batch)
    mod.update()
    held = dict(mod.get_params()[0])
    raw = {n: mod._exec.arg_dict[n]._data for n in mod._param_names}
    moms = {k: v._data for k, v in mod._updater.states.items()}
    blob = mod._updater.get_states()
    saved = pickle.loads(blob)
    want = {k: v.asnumpy() for k, v in held.items()}
    mod.forward_backward(batch)
    mod.update()
    for k in held:
        assert np.array_equal(given[k].asnumpy(), given_np[k]), k
        assert np.array_equal(held[k].asnumpy(), want[k]), k
        assert np.array_equal(np.asarray(raw[k]), want[k]), k
        assert not np.array_equal(mod._exec.arg_dict[k].asnumpy(), want[k])
    for k, m in moms.items():
        new = mod._updater.states[k]
        assert new._data is not m and np.isfinite(new.asnumpy()).all()
        # nothing is donated: a handle taken off the state before the
        # step still reads what the state then held
        assert np.array_equal(np.asarray(m), saved[k]), k
    assert sorted(saved) == sorted(moms)
    mod._updater.set_states(blob)
    mod.forward_backward(batch)
    mod.update()


def test_borrowed_optimizer_shares_the_momenta(monkeypatch):
    """A module bound with `shared_module` borrows the updater, momenta
    included, and its weights start as aliases of the other's: each
    module's step leaves the other's arrays readable, and the two take
    the steps the per-parameter path takes."""
    X, y = _toy_classification(n=32)
    batch = mio.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])

    def run():
        np.random.seed(5)
        mx.random.seed(5)
        first = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        first.bind(data_shapes=[("data", (32, 16))],
                   label_shapes=[("softmax_label", (32,))])
        first.init_params(mx.init.Xavier())
        first.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9})
        second = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        second.bind(data_shapes=[("data", (32, 16))],
                    label_shapes=[("softmax_label", (32,))],
                    shared_module=first)
        assert second._updater is first._updater
        seen = []
        for mod in (first, second, first):
            mod.forward_backward(batch)
            states = dict(mod._updater.states)
            mod.update()
            assert all(mod._updater.states[k] is v
                       for k, v in states.items())
            seen.append({k: v.asnumpy()
                         for k, v in first.get_params()[0].items()})
            seen.append({k: v.asnumpy()
                         for k, v in second.get_params()[0].items()})
        seen.append({k: v.asnumpy() for k, v in first._updater.states.items()})
        return seen
    got = run()
    _per_parameter(monkeypatch)
    want = run()
    for a, b in zip(got, want):
        assert sorted(a) == sorted(b)
        for k in b:
            assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(got[0]["fc1_weight"], got[1]["fc1_weight"])
