"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached
(``jax.experimental.topologies``).  Nothing runs, so these tests say
nothing about results or speed — they say what Mosaic or XLA:TPU would
refuse on a v5e, and whether a program fits its 16 GB, before a chip run
pays to find out.  chip_smoke.py's sizes are the sizes compiled here.

Everything that touches the topology lives in fixtures of THIS file and
runs in the test's own process: only one process at a time may load the
TPU library, every xdist worker imports every test file, and a second
file would land on another worker and skip in silence.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip("no v5e:2x2 topology can be described here: %r"
                        % (e,))
        yield desc
    finally:
        env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip, with JAX's persistent
    compilation cache off while the module's tests run: a described
    compile can be written to it but never read back without a chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _described(tree, sharding):
    """Arrays (or shapes) -> ShapeDtypeStructs placed on the described
    chip: there is no device to hold an array."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _total_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# the KV-cache write ops, in the implementation 'auto' picks on a TPU
# ---------------------------------------------------------------------------

def _impl_auto_picks_on_tpu(monkeypatch, cache):
    """What ``MXNET_CACHE_SCATTER_IMPL=auto`` resolves to where the
    default backend is a TPU — asked with the backend query steered,
    then undone so the compile below sees the real process."""
    import jax
    from mxnet_tpu.ops.cache import _impl_mode
    monkeypatch.delenv("MXNET_CACHE_SCATTER_IMPL", raising=False)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return _impl_mode(cache)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(32, 4096, 2048), (8, 2048, 1024),
                                   (8, 128, 32)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("op,window", [("_cache_write_row", None),
                                       ("_cache_write_rows", 5)])
def test_cache_write_compiles_for_v5e(one_chip, monkeypatch, op, window,
                                      shape, dtype):
    """Both scatter ops compile for the chip at real pool shapes in
    float32 and bfloat16, as the Pallas kernel 'auto' picks there."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    slots, max_len, d = shape
    dt = jnp.dtype(dtype)
    cache = jax.ShapeDtypeStruct(shape, dt)
    impl = _impl_auto_picks_on_tpu(monkeypatch, cache)
    assert impl == "pallas"
    monkeypatch.setenv("MXNET_CACHE_SCATTER_IMPL", impl)
    vec = jax.ShapeDtypeStruct((slots,), jnp.float32)
    if window is None:
        args = (cache, jax.ShapeDtypeStruct((slots, d), dt), vec)
    else:
        args = (cache, jax.ShapeDtypeStruct((slots, window, d), dt),
                vec, vec)
    opdef = get_op(op)
    fn = jax.jit(opdef.bound(opdef.normalize({})), donate_argnums=0)
    compiled = fn.lower(*_described(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # in place: nothing the size of the pool besides the pool itself
    assert compiled.memory_analysis().temp_size_in_bytes \
        < cache.size * dt.itemsize // 8


# ---------------------------------------------------------------------------
# chip_smoke.py's step programs at its sizes
# ---------------------------------------------------------------------------

def _compile_described(program, sharding):
    """Compile one of chip_smoke's (jitted fn, args) pairs with the
    arguments described on the chip."""
    fn, args = program
    return fn.lower(*_described(args, sharding)).compile()


def test_lstm_decode_step_compiles_for_v5e(one_chip):
    """The ``decode`` phase's persistent step program: 32 slots over
    the vocab-10,000 / hidden-1,500 stacked LSTM."""
    import chip_smoke
    from mxnet_tpu.serving.decode import StepProgram
    sz = chip_smoke.SIZES["decode"]
    step, params, state_info = chip_smoke._lstm_model(sz, seed=0)
    prog = StepProgram(step, params, {}, state_info,
                       num_slots=sz["slots"], ctx=mx.cpu(0))
    compiled = _compile_described(chip_smoke.decode_step_program(prog),
                                  one_chip)
    assert _total_bytes(compiled) < HBM_BYTES


def test_kv_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The ``kv`` phase's step program as the engine builds it (select
    pass included), with the cache writes in the kernel 'auto' picks on
    the chip."""
    import jax
    import chip_smoke
    from mxnet_tpu import serving
    sz = chip_smoke.SIZES["kv"]
    pool = jax.ShapeDtypeStruct((sz["slots"], sz["max_len"], sz["d"]),
                                np.float32)
    impl = _impl_auto_picks_on_tpu(monkeypatch, pool)
    monkeypatch.setenv("MXNET_CACHE_SCATTER_IMPL", impl)
    target, params, t_info = chip_smoke._kv_model(sz, seed=0)
    eng = serving.DecodeEngine(target, params, {}, t_info,
                               num_slots=sz["slots"],
                               max_len=sz["max_len"], ctx=mx.cpu(0),
                               start=False)
    try:
        assert [s["op"] for s in eng.selection] \
            == ["_cache_write_row"] * (2 * sz["blocks"])
        compiled = _compile_described(
            chip_smoke.decode_step_program(eng._replicas[0].program),
            one_chip)
    finally:
        eng.close()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_resnet50_module_step_fits_v5e(one_chip):
    """The fused forward+backward program ``Module`` compiles for the
    ``train`` phase, at its batch and in the dtype Module trains in,
    fits one chip's 16 GB."""
    import chip_smoke
    mod = chip_smoke.bound_train_module(chip_smoke.SIZES["train"],
                                        mx.cpu(0))
    compiled = _compile_described(chip_smoke.train_step_program(mod),
                                  one_chip)
    assert mod._exec.arg_dict["data"].dtype == np.float32
    assert _total_bytes(compiled) < HBM_BYTES


def test_resnet50_update_program_compiles_for_v5e(one_chip):
    """The one program ``Module.update`` dispatches a step under SGD
    with momentum: every ResNet-50 parameter that has a gradient, the
    rates and decays as operands.  Nothing is donated (a caller's array
    may share a weight's or a momentum's buffer): new weights and momenta
    live beside the old ones, five copies of the 102 MB (weights,
    gradients, momenta, new weights, new momenta)."""
    import jax
    import chip_smoke
    from mxnet_tpu import optimizer as opt
    mod = chip_smoke.bound_train_module(chip_smoke.SIZES["train"],
                                        mx.cpu(0))
    weights = tuple(mod._exec.arg_dict[n]._data for n in mod._param_names
                    if mod._exec.grad_dict.get(n) is not None)
    n, nbytes = len(weights), sum(w.nbytes for w in weights)
    assert n == 157 and 100e6 < nbytes < 105e6
    vec = jax.ShapeDtypeStruct((n,), np.float32)
    scalar = jax.ShapeDtypeStruct((), np.float32)
    args = _described((weights, weights, weights, vec, vec, scalar, scalar),
                      one_chip)
    compiled = opt._multi_sgd_jit().lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == 0
    assert ma.output_size_in_bytes >= 2 * nbytes
    assert _total_bytes(compiled) < 5.5 * nbytes


# ---------------------------------------------------------------------------
# the static memory planner against the number it exists to predict
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mlp", "lenet", "resnet18"])
def test_planner_peak_within_25pct_of_v5e(one_chip, name):
    """analysis/memory.py's predicted peak against the v5e compiler's
    own memory_analysis() for the same inference program — the
    two-sided calibration pin.  (tests/test_memory.py keeps the CPU
    compiler to what it can hold: it now counts a repacked copy of the
    convolution weights among its temporaries, which no chip pays.)"""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.analysis.memory import plan_memory
    from mxnet_tpu.executor import build_graph_fn
    from test_memory import _zoo
    net, shapes = _zoo(name)
    plan, report = plan_memory(net, shapes)
    assert plan is not None and not report.errors
    g = build_graph_fn(net, net.list_arguments(),
                       net.list_auxiliary_states())
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    avals = _described(
        tuple(tuple(jax.ShapeDtypeStruct(tuple(s), jnp.float32)
                    for s in group) for group in (arg_shapes, aux_shapes)),
        one_chip)
    compiled = jax.jit(lambda a, x: g(a, x, None, False)[0]) \
        .lower(*avals).compile()
    chip = _total_bytes(compiled)
    assert chip > 0
    assert abs(plan["peak_bytes"] - chip) / chip < 0.25, \
        "planner %d vs v5e compiler %d" % (plan["peak_bytes"], chip)


# ---------------------------------------------------------------------------
# the decoder with experts and window caches, at the benchmark's sizes
# ---------------------------------------------------------------------------

def _smallthinker_cfg():
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "smallthinker-21ba3b-8l-bf16.json")) as f:
        return json.load(f)


def test_smallthinker_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """``smallthinker-decode-longdoc``'s step at its own size: 32 slots,
    eight layers at the published widths in bfloat16, rings of 4,096
    rows and caches of 12,288, the pool donated, the cache writes in the
    kernel 'auto' picks on the chip and the expert layers in the grouped
    kernel.  7.93 GB of weights and 3.22 GB of pool; what the step adds
    to them has to stay small."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models import smallthinker
    cfg = _smallthinker_cfg()
    slots, bf = 32, jnp.bfloat16
    step, info = smallthinker.decode_step(cfg, 12288)
    pool = jax.ShapeDtypeStruct((slots,) + tuple(info[0]["shape"]), bf)
    monkeypatch.setenv("MXNET_CACHE_SCATTER_IMPL",
                       _impl_auto_picks_on_tpu(monkeypatch, pool))
    head = mx.sym.argmax(step[0], axis=1)
    serve = mx.sym.Group([head] + [step[i] for i in range(1, len(step))])
    names = serve.list_arguments()
    fn = build_graph_fn(serve, names, [])
    shapes = smallthinker.param_shapes(cfg)
    states = {i["name"]: (slots,) + tuple(i["shape"]) for i in info}
    args = [jax.ShapeDtypeStruct(shapes[n], bf) if n in shapes
            else jax.ShapeDtypeStruct(states[n], bf) if n in states
            else jax.ShapeDtypeStruct((slots,), jnp.float32)
            for n in names]
    jitted = jax.jit(
        lambda *flat: fn(list(flat), [], jax.random.PRNGKey(0), False)[0],
        donate_argnums=tuple(names.index(n) for n in states))
    compiled = jitted.lower(*_described(args, one_chip)).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "moe_grouped" in text        # the experts' grouped kernel
    assert ma.alias_size_in_bytes == 32 * (12 * 4096 + 4 * 12288) * 512 * 2
    assert ma.temp_size_in_bytes < 0.5e9
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("case", ["experts_8192_rows", "attention_global",
                                  "attention_window", "commit_rings"])
def test_smallthinker_prefill_parts_compile_for_v5e(one_chip, case):
    """The parts of the 8,192-position prefill that are new to the chip's
    compiler, each alone (the whole program takes it a minute): the
    grouped expert product over 49,152 sorted pairs, attention (the
    op's platform switch picks the fused kernel for the described
    chip: a ``tpu_custom_call`` and no score tensor in HBM at all, where
    the blockwise path held 0.94 GB), and the prefill's keys and values
    laid into rings and whole caches in place."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import smallthinker
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.serving.slot_state import SlotLayout
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype)
    donate = ()
    if case == "experts_8192_rows":
        op = get_op("_moe_experts")
        fn = op.bound(op.normalize({"top_k": 6}))
        args = (sds(8192, 2560), sds(8192, 64, dtype=jnp.float32),
                sds(64, 768, 2560), sds(64, 768, 2560), sds(64, 768, 2560))
        limit = 1.5e9
    elif case.startswith("attention"):
        op = get_op("_gqa_prefill")
        fn = op.bound(op.normalize({
            "num_heads": 28, "num_kv_heads": 4,
            "window": 4096 if case.endswith("window") else 0}))
        args = (sds(1, 8192, 3584), sds(1, 8192, 512), sds(1, 8192, 512))
        limit = 1e6         # scores and statistics stay in VMEM
    else:
        info = smallthinker.state_info(_smallthinker_cfg(), 12288)

        fn = SlotLayout(info, 32, bf).lay_prefill
        args = ([sds(32, *i["shape"]) for i in info],
                [sds(1, 8192, 512) for _ in info],
                sds(1, dtype=jnp.int32), sds(1, dtype=jnp.int32))
        donate, limit = (0,), 0.2e9
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *_described(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    if case.startswith("attention"):
        assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the decoder with conv rows beside caches, at the benchmark's sizes
# ---------------------------------------------------------------------------

def _lfm2_cfg():
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-8b-a1b-14l-bf16.json")) as f:
        return json.load(f)


def test_lfm2_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """``lfm2-decode-chat``'s step at its own size: 256 slots, fourteen
    layers at the published widths in bfloat16 (the expert bias
    float32), six caches of 1,280 rows and eleven conv rows a slot, the
    whole pool donated, the cache writes in the kernel 'auto' picks on
    the chip and the expert layers in the grouped kernel the op's
    platform switch picks for it.  9.33 GB of weights and 2.04 GB of
    pool; what the step adds to them has to stay small."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models import lfm2
    cfg = _lfm2_cfg()
    slots, bf = 256, jnp.bfloat16
    step, info = lfm2.decode_step(cfg, 1280)
    pool = jax.ShapeDtypeStruct((slots, 1280, 512), bf)
    monkeypatch.setenv("MXNET_CACHE_SCATTER_IMPL",
                       _impl_auto_picks_on_tpu(monkeypatch, pool))
    head = mx.sym.argmax(step[0], axis=1)
    serve = mx.sym.Group([head] + [step[i] for i in range(1, len(step))])
    names = serve.list_arguments()
    fn = build_graph_fn(serve, names, [])
    shapes = lfm2.param_shapes(cfg)
    states = {i["name"]: (slots,) + tuple(i["shape"]) for i in info}
    args = [jax.ShapeDtypeStruct(
                shapes[n], jnp.float32 if n.endswith("expert_bias") else bf)
            if n in shapes
            else jax.ShapeDtypeStruct(states[n], bf) if n in states
            else jax.ShapeDtypeStruct((slots,), jnp.float32)
            for n in names]
    jitted = jax.jit(
        lambda *flat: fn(list(flat), [], jax.random.PRNGKey(0), False)[0],
        donate_argnums=tuple(names.index(n) for n in states))
    compiled = jitted.lower(*_described(args, one_chip)).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "moe_grouped" in text        # the experts' grouped kernel
    assert ma.alias_size_in_bytes \
        == 256 * (6 * 1280 * 512 + 11 * 2 * 2048) * 2
    assert ma.temp_size_in_bytes < 0.5e9
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("case", ["conv_32_prompts", "experts_256_rows",
                                  "commit_rows_and_caches"])
def test_lfm2_parts_compile_for_v5e(one_chip, case):
    """What LFM2 brought to the chip's compiler, each part alone: the
    short convolution over a dispatch of 32 padded prompts of 512 (three
    shifted multiply-adds and the state at each row's own length), the
    step's expert layer over 256 rows (sigmoid scores under a bias,
    SwiGLU, the grouped kernel), and one prefill commit laying keys,
    values and conv rows of 32 prompts into the pool in place."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import lfm2
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.serving.slot_state import SlotLayout
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype)
    donate = ()
    if case == "conv_32_prompts":
        op = get_op("_short_conv_seq")
        fn = op.bound(op.normalize({}))
        args = (sds(32, 512, 6144), sds(32, dtype=jnp.float32),
                sds(3, 2048))
        limit = 0.5e9
    elif case == "experts_256_rows":
        op = get_op("_moe_experts")
        fn = op.bound(op.normalize({
            "top_k": 4, "routing": "sigmoid", "activation": "silu",
            "expert_bias": True}))
        args = (sds(256, 2048), sds(256, 32, dtype=jnp.float32),
                sds(32, 1792, 2048), sds(32, 1792, 2048),
                sds(32, 1792, 2048), sds(32, dtype=jnp.float32))
        limit = 16e6        # gate and up stay in the kernel's VMEM
    else:
        info = lfm2.state_info(_lfm2_cfg(), 1280)
        fn = SlotLayout(info, 256, bf).lay_prefill
        args = ([sds(256, *i["shape"]) for i in info],
                [sds(32, 512, 512) if i.get("cache") else sds(32, 2, 2048)
                 for i in info],
                sds(32, dtype=jnp.int32), sds(32, dtype=jnp.int32))
        donate, limit = (0,), 0.2e9
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *_described(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    if case == "experts_256_rows":
        assert "moe_grouped" in compiled.as_text()


# ---------------------------------------------------------------------------
# the hybrid decoder: a state space's state beside caches and conv rows
# ---------------------------------------------------------------------------

def _falcon_cfg():
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "falcon-h1-34b-4l-bf16.json")) as f:
        return json.load(f)


def test_falcon_h1_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """``falcon-h1-decode-chat``'s step at its own size: 256 slots, four
    layers at the published widths in bfloat16, a layer's two caches of
    1,280 rows, conv row and state space row, the whole pool donated.
    8.79 GB of weights and a 4.86 GB pool; the state space's update is
    one loop fusion a layer that reads and writes the state in place, so
    no float32 copy of the 2.15 GB of state appears among the
    temporaries."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models import falcon_h1
    cfg = _falcon_cfg()
    slots, bf = 256, jnp.bfloat16
    step, info = falcon_h1.decode_step(cfg, 1280)
    pool = jax.ShapeDtypeStruct((slots, 1280, 512), bf)
    monkeypatch.setenv("MXNET_CACHE_SCATTER_IMPL",
                       _impl_auto_picks_on_tpu(monkeypatch, pool))
    head = mx.sym.argmax(step[0], axis=1)
    serve = mx.sym.Group([head] + [step[i] for i in range(1, len(step))])
    names = serve.list_arguments()
    fn = build_graph_fn(serve, names, [])
    shapes = falcon_h1.param_shapes(cfg)
    states = {i["name"]: (slots,) + tuple(i["shape"]) for i in info}
    args = [jax.ShapeDtypeStruct(shapes[n], bf) if n in shapes
            else jax.ShapeDtypeStruct(states[n], bf) if n in states
            else jax.ShapeDtypeStruct((slots,), jnp.float32)
            for n in names]
    jitted = jax.jit(
        lambda *flat: fn(list(flat), [], jax.random.PRNGKey(0), False)[0],
        donate_argnums=tuple(names.index(n) for n in states))
    compiled = jitted.lower(*_described(args, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == 256 * 4 * (
        2 * 1280 * 512 + 3 * 5120 + 32 * 128 * 256) * 2
    assert ma.temp_size_in_bytes < 0.1e9
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("case", ["scan_32_prompts", "prefill_8_prompts"])
def test_falcon_h1_prefill_compiles_for_v5e(one_chip, case):
    """The chunked scan over a dispatch of 32 padded prompts of 512 alone
    (4 chunks of 128 unrolled, a state carried in float32, no state a
    position),
    and the whole prefill program over 8: its temporaries beside 8.79 GB
    of weights and the 4.86 GB pool leave it room on the chip."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype)
    if case == "scan_32_prompts":
        op = get_op("_ssd_scan")
        fn = op.bound(op.normalize({"num_groups": 2, "chunk": 128}))
        args = (sds(32, 512, 4096), sds(32, 512, 32), sds(32, 512, 512),
                sds(32, 512, 512), sds(32, dtype=jnp.float32), sds(32),
                sds(32), sds(32))
        # 0.38 GB: a chunk's float32 products and state beside y
        limit = 0.6e9
    else:
        from mxnet_tpu.executor import build_graph_fn
        from mxnet_tpu.models import falcon_h1
        cfg = _falcon_cfg()
        pf = falcon_h1.prefill(cfg)(512)
        names = pf.list_arguments()
        graph = build_graph_fn(pf, names, [])
        shapes = falcon_h1.param_shapes(cfg)
        args = [sds(*shapes[n]) if n in shapes
                else sds(8, 512, dtype=jnp.float32) if n == "prompt"
                else sds(8, dtype=jnp.float32) for n in names]

        def fn(*flat):
            return graph(list(flat), [], jax.random.PRNGKey(0), False)[0]
        limit = 1.0e9
    compiled = jax.jit(fn).lower(*_described(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < limit
