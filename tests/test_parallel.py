"""Parallelism tests on the 8-device virtual CPU mesh (conftest forces
xla_force_host_platform_device_count=8 — the reference's trick of testing
multi-device semantics on CPU, tests/python/unittest/test_multi_device_exec.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio
from mxnet_tpu.parallel import (make_mesh, ShardingPlan, data_parallel_plan,
                                ring_attention, blockwise_attention,
                                pipeline_shard_map)


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    act1 = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _toy(n=256, d=16, k=4, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d) * 3
    X = np.stack([centers[i % k] + rng.randn(d) * .5 for i in range(n)]
                 ).astype(np.float32)
    y = np.array([i % k for i in range(n)], dtype=np.float32)
    return X, y


def test_make_mesh():
    import jax
    mesh = make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh = make_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["dp"] == len(jax.devices()) // 2


def _train(plan, seed=7, steps=6):
    X, y = _toy()
    np.random.seed(seed)
    it = mio.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    if plan is not None:
        mod.set_sharding_plan(plan)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "rescale_grad": 1. / 64})
    done = 0
    while done < steps:
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
            done += 1
            if done >= steps:
                break
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def test_data_parallel_matches_single_device():
    """dp-sharded training must be numerically identical to unsharded —
    the psum compiled in by XLA replaces kvstore reduce exactly."""
    ref = _train(None)
    dp = _train(data_parallel_plan())
    for k in ref:
        np.testing.assert_allclose(ref[k], dp[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_tensor_parallel_matches():
    """fc weights sharded over tp: same numbers, sharded memory."""
    mesh = make_mesh({"dp": 4, "tp": 2})
    plan = ShardingPlan(mesh, batch_axis="dp",
                        param_rules=[(r"fc\d_weight", ("tp", None))])
    tp = _train(plan)
    ref = _train(None)
    for k in ref:
        np.testing.assert_allclose(ref[k], tp[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_sharded_param_placement():
    mesh = make_mesh({"dp": 4, "tp": 2})
    plan = ShardingPlan(mesh, batch_axis="dp",
                        param_rules=[("fc1_weight", ("tp", None))])
    X, y = _toy(n=64)
    it = mio.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.set_sharding_plan(plan)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    w = mod._executor.arg_dict["fc1_weight"]._data
    assert len(w.sharding.device_set) == 8
    # sharded on dim 0 over tp=2: each device holds a (16, 16) shard
    shard_shape = w.sharding.shard_shape(w.shape)
    assert shard_shape == (16, 16)


def test_dp_fit_multi_epoch():
    """Regression: the epoch-boundary get_params/set_params round-trip in
    fit() must not strip the mesh sharding from params (copyto preserves
    destination placement)."""
    X, y = _toy()
    it = mio.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.set_sharding_plan(data_parallel_plan())
    mod.fit(it, num_epoch=3, optimizer_params={"learning_rate": 0.5})
    acc = mod.score(mio.NDArrayIter(X, y, batch_size=64), "acc")[0][1]
    assert acc > 0.9, acc
    w = mod._executor.arg_dict["fc1_weight"]._data
    assert len(w.sharding.device_set) == 8


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((sq, sk), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 16, 2, 8).astype(np.float32)
    k = rng.randn(2, 16, 2, 8).astype(np.float32)
    v = rng.randn(2, 16, 2, 8).astype(np.float32)
    out = np.asarray(blockwise_attention(q, k, v, block_size=4, causal=causal))
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_blockwise_attention_fully_masked_block():
    """A causal block whose kv positions all exceed the q positions must
    contribute ZERO (not exp(0)=1 per masked lane while m is at the init)."""
    rng = np.random.RandomState(2)
    q = rng.randn(1, 4, 1, 8).astype(np.float32)
    k = rng.randn(1, 4, 1, 8).astype(np.float32)
    v = rng.randn(1, 4, 1, 8).astype(np.float32)
    # kv_offset beyond every q position -> every score masked -> zeros out
    out = np.asarray(blockwise_attention(q, k, v, block_size=4, causal=True,
                                         q_offset=0, kv_offset=100))
    np.testing.assert_allclose(out, np.zeros_like(out))
    # bf16 inputs must not overflow the mask constant in the accumulators
    import jax.numpy as jnp
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    outb = np.asarray(blockwise_attention(qb, kb, vb, block_size=2,
                                          causal=True).astype(jnp.float32))
    ref = _dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(outb, ref, rtol=0.1, atol=0.05)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    import jax
    mesh = make_mesh({"sp": 8})
    rng = np.random.RandomState(1)
    q = rng.randn(2, 32, 2, 8).astype(np.float32)
    k = rng.randn(2, 32, 2, 8).astype(np.float32)
    v = rng.randn(2, 32, 2, 8).astype(np.float32)
    out = np.asarray(ring_attention(q, k, v, mesh, axis_name="sp",
                                    causal=causal))
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    import jax.numpy as jnp
    mesh = make_mesh({"pp": 8})
    rng = np.random.RandomState(2)
    # 8 stages, each y = tanh(x @ w_i)
    Ws = rng.randn(8, 16, 16).astype(np.float32) * 0.5
    x = rng.randn(32, 16).astype(np.float32)

    def stage(w, xx):
        return jnp.tanh(xx @ w)

    out = np.asarray(pipeline_shard_map(stage, mesh, Ws, x, n_microbatch=4))
    ref = x
    for i in range(8):
        ref = np.tanh(ref @ Ws[i])
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_two_bit_compression_error_feedback():
    """compute_expected_2bit_quantization math from the reference's
    test_kvstore.py: quantize to {-t, 0, +t} with residual feedback."""
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", mx.nd.zeros((4,)))
    g = mx.nd.array([0.7, -0.6, 0.2, 0.0])
    kv.push("w", g)
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, -0.5, 0.0, 0.0])
    # residual [0.2, -0.1, 0.2, 0.0] feeds forward: push 0.4 -> 0.2+0.4 >= t
    kv2 = mx.kv.create("device")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("w", mx.nd.zeros((4,)))
    kv2.push("w", g)
    kv2.push("w", mx.nd.array([0.4, 0.0, 0.4, 0.0]))
    kv2.pull("w", out=out)
    # second push quantizes residual+g2 = [0.6, -0.1, 0.6, 0] -> [0.5,0,0.5,0]
    # store overwrites (no updater): holds the last quantized push
    np.testing.assert_allclose(out.asnumpy(), [0.5, 0.0, 0.5, 0.0])


def test_pipeline_training_matches_unpipelined():
    """GPipe backward: a 4-stage pipeline's loss trajectory must match the
    same stack trained unpipelined on one device (VERDICT r2 task 9)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import pipeline_train_step

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.default_rng(0)
    D = 8
    Ws = jnp.asarray(rng.standard_normal((4, D, D)).astype(np.float32) * 0.3)
    X = jnp.asarray(rng.standard_normal((16, D)).astype(np.float32))
    Y = jnp.asarray((np.arange(16) % D).astype(np.float32))

    def stage(w, x):
        return jnp.tanh(x @ w)

    def loss_fn(out, labels):
        logp = jax.nn.log_softmax(out)
        return -logp[jnp.arange(out.shape[0]),
                     labels.astype(jnp.int32)].mean()

    step = pipeline_train_step(stage, loss_fn, mesh, n_microbatch=4,
                               optimizer=lambda p, g: p - 0.5 * g)
    params = Ws
    piped_losses = []
    for _ in range(5):
        loss, params = step(params, X, Y)
        piped_losses.append(float(loss))

    # unpipelined reference: same math, plain composition + grad
    def forward_loss(ws, x, labels):
        h = x
        for i in range(4):
            h = stage(ws[i], h)
        return loss_fn(h, labels)

    ref = Ws
    ref_losses = []
    gfn = jax.jit(jax.value_and_grad(forward_loss))
    for _ in range(5):
        loss, g = gfn(ref, X, Y)
        ref_losses.append(float(loss))
        ref = ref - 0.5 * g

    np.testing.assert_allclose(piped_losses, ref_losses, rtol=1e-4,
                               atol=1e-5)
    assert piped_losses[-1] < piped_losses[0]  # actually learning
    np.testing.assert_allclose(np.asarray(params), np.asarray(ref),
                               rtol=1e-3, atol=1e-4)


def test_hetero_pipeline_lm_matches_unpipelined():
    """Heterogeneous 3-stage LM (embed -> body -> head: different param
    pytrees AND activation shapes per stage) trains through the packed
    GPipe pipeline and matches the unpipelined composition exactly
    (VERDICT r3 item #9)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import hetero_pipeline_train_step

    devs = np.array(jax.devices()[:3])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.default_rng(0)
    V, D, H, T, mb, M = 11, 6, 9, 5, 4, 4
    B = mb * M
    p_embed = {"emb": jnp.asarray(
        rng.standard_normal((V, D)).astype(np.float32) * 0.3)}
    p_body = {"w1": jnp.asarray(
        rng.standard_normal((D, H)).astype(np.float32) * 0.3),
        "b1": jnp.zeros((H,), jnp.float32)}
    p_head = {"wo": jnp.asarray(
        rng.standard_normal((H, V)).astype(np.float32) * 0.3)}

    def embed(p, x):                        # (mb, T) float ids -> (mb,T,D)
        ids = jnp.clip(x.astype(jnp.int32), 0, V - 1)
        return jnp.take(p["emb"], ids, axis=0)

    def body(p, h):                         # (mb,T,D) -> (mb,T,H)
        return jnp.tanh(h @ p["w1"] + p["b1"])

    def head(p, h):                         # (mb,T,H) -> (mb,T,V)
        return h @ p["wo"]

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits)
        lab = labels.astype(jnp.int32)
        return -jnp.take_along_axis(logp, lab[..., None],
                                    axis=-1).mean()

    X = jnp.asarray(rng.integers(0, V, (B, T)).astype(np.float32))
    Y = jnp.asarray(rng.integers(0, V, (B, T)).astype(np.float32))
    stages = [embed, body, head]
    params0 = [p_embed, p_body, p_head]

    step, pack, unpack = hetero_pipeline_train_step(
        stages, params0, X[:mb], loss_fn, mesh, n_microbatch=M,
        optimizer=lambda p, g: p - 0.5 * g)
    packed = pack(params0)
    piped_losses = []
    for _ in range(4):
        loss, packed = step(packed, X, Y)
        piped_losses.append(float(loss))

    def forward_loss(ps, x, labels):
        h = embed(ps[0], x)
        h = body(ps[1], h)
        return loss_fn(head(ps[2], h), labels)

    ref = params0
    ref_losses = []
    gfn = jax.jit(jax.value_and_grad(forward_loss))
    for _ in range(4):
        loss, g = gfn(ref, X, Y)
        ref_losses.append(float(loss))
        ref = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg, ref, g)

    np.testing.assert_allclose(piped_losses, ref_losses, rtol=1e-4,
                               atol=1e-5)
    assert piped_losses[-1] < piped_losses[0]
    got = unpack(packed)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_pipeline_module_trains():
    """PipelineModule: symbol-defined stage, Module-style driving."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import PipelineModule
    from mxnet_tpu.io import DataBatch

    stage = mx.sym.Activation(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              no_bias=True, name="w"), act_type="tanh")
    pm = PipelineModule(stage, n_stages=4, n_microbatch=4)
    pm.bind(data_shapes=[("data", (16, 8))])
    # wide init: a deep tanh chain with near-zero weights has vanishing
    # gradients, which would test patience rather than the pipeline
    pm.init_params(initializer=mx.init.Uniform(0.6))
    pm.init_optimizer(learning_rate=1.0)
    rng = np.random.default_rng(1)
    X = rng.standard_normal((16, 8)).astype(np.float32)
    Y = (np.arange(16) % 8).astype(np.float32)
    losses = []
    for _ in range(25):
        pm.forward_backward(DataBatch(data=[mx.nd.array(X)],
                                      label=[mx.nd.array(Y)]))
        pm.update()
        losses.append(pm.loss)
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_moe_dispatch_matches_dense():
    """Expert-parallel all_to_all routing == dense per-token computation
    (capacity >= tokens: lossless)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.moe import moe_dispatch

    E = 4
    mesh = Mesh(np.array(jax.devices()[:E]), ("ep",))
    rng = np.random.default_rng(0)
    n, d = 32, 8
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    gl = jnp.asarray(rng.standard_normal((n, E)).astype(np.float32))
    W = jnp.asarray(rng.standard_normal((E, d, d)).astype(np.float32) * 0.3)

    def expert(w, toks):
        return jnp.tanh(toks @ w)

    out, choice = moe_dispatch(expert, mesh, W, x, gl, capacity=n)
    out, choice = np.asarray(out), np.asarray(choice)

    gate = np.asarray(jax.nn.softmax(gl, axis=1))
    expect = np.zeros((n, d), np.float32)
    for i in range(n):
        e = int(np.argmax(np.asarray(gl)[i]))
        assert choice[i] == e
        expect[i] = np.tanh(np.asarray(x)[i] @ np.asarray(W)[e]) * gate[i, e]
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_overflow():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.moe import moe_dispatch

    E = 2
    mesh = Mesh(np.array(jax.devices()[:E]), ("ep",))
    n, d = 8, 4
    x = jnp.ones((n, d), jnp.float32)
    # every token picks expert 0
    gl = jnp.tile(jnp.asarray([[5.0, -5.0]], jnp.float32), (n, 1))
    W = jnp.ones((E, d, d), jnp.float32)

    out, _ = moe_dispatch(lambda w, t: t @ w, mesh, W, x, gl, capacity=1)
    out = np.asarray(out)
    # per source device (4 tokens each), only 1 fits expert 0's quota
    nz = (np.abs(out).sum(1) > 0).reshape(E, n // E)
    assert (nz.sum(axis=1) == 1).all()


def test_moe_layer_trains():
    """MoELayer is differentiable end-to-end (grads reach expert params)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.moe import MoELayer

    E = 4
    mesh = Mesh(np.array(jax.devices()[:E]), ("ep",))
    layer = MoELayer(mesh, num_experts=E, d_model=8, d_hidden=16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))

    def loss(params):
        layer.params = params
        out, _ = layer(x)
        return jnp.mean((out - y) ** 2)

    g = jax.grad(loss)(layer.params)
    for k in ("w1", "w2"):
        assert float(jnp.abs(g[k]).sum()) > 0, k


def test_hetero_pipeline_module_resnet_stages():
    """VERDICT r4 item #6: an embed->body->head conv net WITH BatchNorm
    trains through PipelineModule at n=4 from a LIST of stage symbols,
    activations at true per-edge shapes (no max_act padding), and the
    pipelined loss matches a serial per-microbatch execution of the same
    stage functions exactly (the correct reference: BN uses microbatch
    statistics in both)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import PipelineModule
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.io import DataBatch

    def conv_bn(x, nf, name, stride=(1, 1)):
        c = mx.sym.Convolution(x, num_filter=nf, kernel=(3, 3),
                               stride=stride, pad=(1, 1), no_bias=True,
                               name=name + "_conv")
        b = mx.sym.BatchNorm(c, fix_gamma=False, name=name + "_bn")
        return mx.sym.Activation(b, act_type="relu")

    d = mx.sym.Variable("data")
    embed = conv_bn(d, 8, "embed")                      # (mb,3,H,W)->(mb,8,H,W)
    body = conv_bn(mx.sym.Variable("data"), 8, "body", stride=(2, 2))
    head_in = mx.sym.Variable("data")
    pooled = mx.sym.Pooling(head_in, global_pool=True, kernel=(2, 2),
                            pool_type="avg")
    head = mx.sym.FullyConnected(mx.sym.Flatten(pooled), num_hidden=5,
                                 name="head_fc")
    # 4 stages with CHANGING activation shapes: 3x16x16 -> 8x16x16 ->
    # 8x8x8 -> 8x4x4 -> 5 logits
    body2 = conv_bn(mx.sym.Variable("data"), 8, "body2", stride=(2, 2))
    stages = [embed, body, body2, head]

    B, mb = 8, 2
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (B, 3, 16, 16)).astype(np.float32)
    Y = (np.arange(B) % 5).astype(np.float32)

    pm = PipelineModule(stages, n_microbatch=4)
    pm.bind(data_shapes=[("data", (B, 3, 16, 16))])
    pm.init_params(seed=0)
    import copy
    params0 = copy.deepcopy(pm._params)
    aux0 = copy.deepcopy(pm._aux)
    pm.init_optimizer(learning_rate=0.05)
    pm.forward_backward(DataBatch(data=[mx.nd.array(X)],
                                  label=[mx.nd.array(Y)]))
    pm.update()
    first = pm.loss

    # serial per-microbatch reference with the SAME stage functions
    metas = pm._stage_meta
    def serial_loss(params, aux, X, Y):
        outs = []
        aux = [dict(a) for a in aux]
        for k in range(4):                      # n_microbatch
            x = jnp.asarray(X[k * mb:(k + 1) * mb])
            for j, meta in enumerate(metas):
                args = tuple(x if n == "data" else params[j][n]
                             for n in meta["arg_names"])
                auxs = tuple(aux[j][n] for n in meta["aux_names"])
                (x,), new_aux = meta["graph_fn"](args, auxs, None, True)
                aux[j] = dict(zip(meta["aux_names"], new_aux))
            outs.append(x)
        logits = jnp.concatenate(outs).reshape(len(Y), -1)
        logp = jax.nn.log_softmax(logits)
        lab = jnp.asarray(Y).astype(jnp.int32)
        return -logp[jnp.arange(len(Y)), lab].mean()

    ref = float(serial_loss(params0, aux0, X, Y))
    assert abs(first - ref) < 1e-4, (first, ref)

    # and it trains
    losses = [first]
    for _ in range(7):
        pm.forward_backward(DataBatch(data=[mx.nd.array(X)],
                                      label=[mx.nd.array(Y)]))
        pm.update()
        losses.append(pm.loss)
    assert losses[-1] < losses[0], losses

    # aux (BN moving stats) actually updated
    _, aux_now = pm.get_params()
    moved = sum(float(jnp.abs(aux_now[j][n] - aux0[j][n]).max())
                for j in range(4) for n in aux0[j])
    assert moved > 0, "BatchNorm moving stats never updated"


def test_hetero_pipeline_aux_matches_serial():
    """BN moving stats after ONE pipelined step equal the serial
    per-microbatch execution exactly — warmup/drain ticks must not touch
    aux (they used to decay moving_var toward zero and re-count the last
    microbatch)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import PipelineModule
    from mxnet_tpu.io import DataBatch

    def conv_bn(nf, name):
        x = mx.sym.Variable("data")
        c = mx.sym.Convolution(x, num_filter=nf, kernel=(3, 3),
                               stride=(1, 1), pad=(1, 1), no_bias=True,
                               name=name + "_conv")
        b = mx.sym.BatchNorm(c, fix_gamma=False, name=name + "_bn")
        return mx.sym.Activation(b, act_type="relu")

    head = mx.sym.FullyConnected(
        mx.sym.Flatten(mx.sym.Variable("data")), num_hidden=3)
    stages = [conv_bn(4, "s0"), conv_bn(4, "s1"), head]
    B, mb = 6, 2
    rng = np.random.RandomState(3)
    X = rng.uniform(-1, 1, (B, 2, 6, 6)).astype(np.float32)
    Y = (np.arange(B) % 3).astype(np.float32)
    pm = PipelineModule(stages, n_microbatch=3, n_stages=None)
    pm.bind(data_shapes=[("data", (B, 2, 6, 6))])
    pm.init_params()
    import copy
    params0 = copy.deepcopy(pm._params)
    aux0 = copy.deepcopy(pm._aux)
    pm.init_optimizer(learning_rate=0.0)   # isolate aux updates
    pm.forward_backward(DataBatch(data=[mx.nd.array(X)],
                                  label=[mx.nd.array(Y)]))
    pm.update()
    _, aux_now = pm.get_params()

    # serial reference: thread aux through the stages per microbatch
    metas = pm._stage_meta
    aux_ref = [dict(a) for a in aux0]
    for k in range(3):
        x = jnp.asarray(X[k * mb:(k + 1) * mb])
        for j, meta in enumerate(metas):
            args = tuple(x if n == "data" else params0[j][n]
                         for n in meta["arg_names"])
            auxs = tuple(aux_ref[j][n] for n in meta["aux_names"])
            (x,), new_aux = meta["graph_fn"](args, auxs, None, True)
            aux_ref[j] = dict(zip(meta["aux_names"], new_aux))
    for j in range(3):
        for n in aux_ref[j]:
            np.testing.assert_allclose(
                np.asarray(aux_now[j][n]), np.asarray(aux_ref[j][n]),
                rtol=1e-5, atol=1e-6, err_msg="stage %d %s" % (j, n))


def test_multi_tensor_update_keeps_sharding_on_four_devices():
    """One update program over replicated and tp-sharded parameters
    (ISSUE 26): weights and momenta come back with the sharding they
    went in with, and equal the one-device run."""
    import jax
    X, y = _toy(n=64)
    batch = mio.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])

    def run(plan):
        np.random.seed(11)
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        if plan is not None:
            mod.set_sharding_plan(plan)
        mod.bind(data_shapes=[("data", (64, 16))],
                 label_shapes=[("softmax_label", (64,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3,
            "rescale_grad": 1. / 64})
        shardings = []
        for _ in range(3):
            mod.forward_backward(batch)
            mod.update()
            shardings.append(
                {n: (mod._exec.arg_dict[n]._data.sharding,
                     mod._updater.states[i]._data.sharding)
                 for i, n in enumerate(mod._param_names)})
        params = {n: mod._exec.arg_dict[n].asnumpy()
                  for n in mod._param_names}
        moms = {n: mod._updater.states[i].asnumpy()
                for i, n in enumerate(mod._param_names)}
        return mod, shardings, params, moms

    four = jax.devices()[:4]
    for plan in (data_parallel_plan(devices=four),
                 ShardingPlan(make_mesh({"dp": 2, "tp": 2}, devices=four),
                              batch_axis="dp",
                              param_rules=[(r"fc1_weight", ("tp", None))])):
        mod, shardings, params, moms = run(plan)
        for name in mod._param_names:
            w_sh, m_sh = shardings[0][name]
            want = plan.param_sharding(name, params[name].shape)
            assert w_sh.is_equivalent_to(want, params[name].ndim), name
            assert len(w_sh.device_set) == 4
            for later in shardings[1:]:
                assert later[name][0].is_equivalent_to(w_sh,
                                                       params[name].ndim)
                assert later[name][1].is_equivalent_to(m_sh,
                                                       params[name].ndim)
            # the momentum lives where its weight lives, from the first
            # step (its zeros are created on one device, uncommitted)
            assert m_sh.is_equivalent_to(w_sh, params[name].ndim), name
        _mod, _sh, ref_params, ref_moms = run(None)
        for name in ref_params:
            np.testing.assert_allclose(params[name], ref_params[name],
                                       rtol=1e-4, atol=1e-5, err_msg=name)
            np.testing.assert_allclose(moms[name], ref_moms[name],
                                       rtol=1e-4, atol=1e-5, err_msg=name)
