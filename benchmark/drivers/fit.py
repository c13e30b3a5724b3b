"""Traffic kind ``fit``: one ``Module.fit`` call over a synthetic
device-resident ``DataIter``, ended by the clock.

The first ``WARM`` steps of that same call are set-up: they compile (the
step compiles at its first and at its second call), and the first three
are what the plain reference follows.  The window opens when step
``WARM`` has completed and closes with the step during which the clock
ran out; everything in between counts.
"""
import gc
import time

from benchmark import harness

WARM = 4


class _ClockedIter(object):
    """The one batch, again and again, until the window's end is past.
    ``Module.fit`` asks for a batch before it runs the one before, so
    the step that follows the refusal is the last of the call."""

    def __init__(self, mx, data, label, descs):
        self.batch = mx.io.DataBatch(data=[data], label=[label])
        self.provide_data = [mx.io.DataDesc(*descs[0])]
        self.provide_label = [mx.io.DataDesc(*descs[1])]
        self.t_end = None
        self.refused = False

    def __iter__(self):
        return self

    def __next__(self):
        if self.t_end is not None and time.perf_counter() >= self.t_end:
            self.refused = True
            raise StopIteration
        return self.batch

    next = __next__

    def reset(self):
        pass


def run(env):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import _wrap

    cfg, tr, cfgm = env.cfg, env.traffic, env.cfg_mod
    spans, counters = env.spans, env.counters
    batch = tr["batch"]
    lr, momentum = tr["learning_rate"], tr["momentum"]
    ctx = mx.Context(env.platform, 0)
    mod = mx.mod.Module(cfgm.build_symbol(cfg), context=ctx)
    # (name, shape) of the data and of the label, as the graph names them
    descs = cfgm.input_descs(cfg, batch)
    args, aux = cfgm.init_params(cfg, env.seed)
    shardings = None
    if tr.get("plan"):
        from jax.sharding import NamedSharding, PartitionSpec
        from mxnet_tpu.parallel.mesh import ShardingPlan, make_mesh
        mesh = make_mesh(tr["plan"], devices=env.devices)
        plan = ShardingPlan(mesh, batch_axis=next(iter(tr["plan"])))
        mod.set_sharding_plan(plan)
        shardings = tuple(plan.data_sharding(shape) for _n, shape in descs)
        args, aux = jax.device_put(
            (args, aux), NamedSharding(mesh, PartitionSpec()))
    data, label = cfgm.make_batch(cfg, env.seed, batch, shardings)
    w0 = jax.tree_util.tree_map(jnp.copy, args)
    it = _ClockedIter(mx, _wrap(data, ctx), _wrap(label, ctx), descs)

    @jax.jit
    def cross_entropy(probs, lab):
        p = jnp.take_along_axis(probs, lab.astype(jnp.int32)[:, None],
                                axis=1)[:, 0]
        return -jnp.mean(jnp.log(p))

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}

    @jax.jit
    def change_norms(now, before):
        return {k: jnp.sqrt(jnp.sum(jnp.square(now[k] - before[k])))
                for k in now}

    spans.wrap(mod, "forward_backward", "Module.forward_backward")
    spans.wrap(mod, "update", "Module.update")
    spans.wrap(mod, "update_metric", "Module.update_metric")

    got = {"losses": [], "grad1": None, "delta": None}
    st = {"t0": None, "ends": [], "setup_s": None,
          "compiles0": None, "compiles_in_window": 0}
    tracer = harness.Tracer(env.cell, spans) if env.trace else None

    def params_now():
        return {n: mod._exec.arg_dict[n]._data for n in mod._param_names}

    def on_batch_end(param):
        i = param.nbatch
        out = mod.get_outputs()[0]._data
        if i < 3:
            got["losses"].append(cross_entropy(out, label))
        if i == 0:
            names = mod._param_names
            got["grad1"] = norms({
                names[k]: v._data / (-lr)
                for k, v in mod._updater.states.items()})
        if i == 2:
            got["delta"] = change_norms(params_now(), w0)
        if i < WARM - 1:
            return
        jax.block_until_ready(out)
        if i == WARM - 1:
            jax.block_until_ready(params_now())
            st["compiles0"] = counters.snapshot()
            st["t0"] = time.perf_counter()
            st["setup_s"] = st["t0"] - env.t_process
            it.t_end = st["t0"] + env.seconds
            return
        if it.refused:
            jax.block_until_ready(params_now())
            st["compiles_in_window"] = counters.since(
                st["compiles0"])["requests"]
        now = time.perf_counter()
        st["ends"].append(now)
        if tracer is None:
            return
        if tracer.started is None:
            tracer.start()
        elif tracer.active and (
                now - tracer.started >= harness.TRACE_SECONDS
                or it.refused):
            tracer.stop()

    mod.fit(it, eval_metric="acc", batch_end_callback=on_batch_end,
            optimizer="sgd",
            optimizer_params=(("learning_rate", lr), ("momentum", momentum)),
            arg_params={k: _wrap(v, ctx) for k, v in args.items()},
            aux_params={k: _wrap(v, ctx) for k, v in aux.items()},
            num_epoch=1)
    if tracer is not None and tracer.active:
        tracer.stop()
    ends = st["ends"]
    window_s = ends[-1] - st["t0"]
    obs = {
        "setup_s": st["setup_s"], "window_s": window_s,
        "e2e": {"train_images_per_s": len(ends) * batch / window_s},
        "attempted": len(ends), "failed": 0,
        "compiles_in_window": st["compiles_in_window"],
        "compile_setup": st["compiles0"],
        "counts": {"steps": len(ends), "batch": batch,
                   "chips": len(env.devices)},
        "step_s": [b - a for a, b in zip([st["t0"]] + ends, ends)],
        "window": (st["t0"], ends[-1]),
        # one chip's share of a step: its rows, and every weight
        "required": cfgm.step_required(cfg, batch // len(env.devices)),
    }
    env.log("steps in window %d of %d images; step samples %d"
            % (len(ends), batch, len(ends)))
    if tracer is not None:
        obs["traced"] = {"steps": sum(
            1 for t in ends if tracer.started < t <= tracer.stopped)}
    obs["memory_peak_bytes"] = harness.memory_peak_bytes(env.devices, env.log)
    program = {"losses": [float(x) for x in got["losses"]],
               "grad1": {k: float(v) for k, v in got["grad1"].items()},
               "delta": {k: float(v) for k, v in got["delta"].items()}}

    # ---- the program's state goes, then the reference takes three steps
    del mod, it, got
    gc.collect()
    t_ref = time.perf_counter()
    ref = env.ref_mod.first_steps(w0, data, label, lr, momentum, steps=3)
    env.log("reference took three steps in %.1f s"
            % (time.perf_counter() - t_ref))
    obs["checks"] = compare(program, ref, env.correct["limits"])
    env.log("logged and not compared: %s" % (obs["checks"].logged,))
    if env.calibrate is not None:
        # benchmark/calibrate.py reads the control and the faults here,
        # while the weights and the batch of this seed are at hand
        env.calibrate(program=program, ref=ref, compare=compare,
                      rerun=lambda **kw: env.ref_mod.first_steps(
                          w0, data, label, lr, momentum, steps=3, **kw))
    if tracer is not None:
        obs["trace"] = tracer.reduce()
    return obs


def compare(program, ref, limits):
    """The program's first three steps against the reference's: each
    step's loss, and the gap of norms of the first gradient and of the
    parameters' change after the three, at the median leaf and at the
    worst.  A number that ``limits`` (the cell's file under ``correct/``)
    names is compared; the others are logged (PERF.md section 2 says why
    each is on its side)."""
    loss_gap = [abs(a - b) / abs(b)
                for a, b in zip(program["losses"], ref["losses"])]
    grad = harness.leaf_gaps(program["grad1"], ref["grad1"])
    # a leaf whose gradient is nought to rounding moves by round-off alone
    floor = 1e-3 * harness.percentile(list(ref["grad1"].values()), 50)
    moved = {k: v for k, v in ref["delta"].items()
             if ref["grad1"][k] >= floor}
    delta = harness.leaf_gaps(program["delta"], moved)
    numbers = {"loss_step%d_rel_gap" % (i + 1): g
               for i, g in enumerate(loss_gap)}
    for name, gaps in (("grad1", grad), ("delta", delta)):
        numbers[name + "_median_leaf_gap"] = harness.percentile(
            list(gaps.values()), 50)
        numbers[name + "_worst_leaf_gap"] = max(gaps.values())
    checks = harness.Checks()
    for name in sorted(limits):
        checks.add(name, numbers[name], limits[name])
    checks.logged = {k: v for k, v in numbers.items() if k not in limits}
    checks.logged.update(
        reference_losses=ref["losses"],
        grad1_worst_leaf=max(grad, key=grad.get),
        delta_worst_leaf=max(delta, key=delta.get),
        leaves_not_moved=sorted(set(ref["delta"]) - set(moved)))
    return checks
