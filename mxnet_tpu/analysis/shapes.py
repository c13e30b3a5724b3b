"""Shape/dtype abstract interpretation with per-node provenance.

This is ``symbol._infer_graph`` (the infer_graph_attr_pass.cc analog)
re-run as a *diagnosing* pass: same forward fixed point over
``jax.eval_shape``, but instead of raising one bare ``MXNetError`` at
the first failure it keeps walking, and every failure becomes a
Diagnostic that names the node, shows the concrete input shapes that
reached it, and traces where they flowed from — "node `fc1`
(FullyConnected): ...; inputs: data=(8, 3, 224, 224)  [data -> conv0 ->
fc1]" instead of a stack trace out of executor.py.

Dynamic dims (0/None entries in ``data_shapes``) are abstracted to a
representative concrete size for interpretation — the smallest
configured seq bucket when a policy is present, else 2 — and noted, so
shape errors found here hold for the whole family of shapes serving
will actually dispatch.
"""
from __future__ import annotations

import numpy as _np

from .core import AnalysisPass, register_pass
from .diagnostics import Diagnostic, Severity

__all__ = ["ShapeDtypePass"]

_REPR_DYN = 2   # stand-in extent for a dynamic dim with no bucket grid


def _fmt_shape(s):
    return "?" if s is None else str(tuple(s))


@register_pass
class ShapeDtypePass(AnalysisPass):
    name = "shapes"

    def run(self, ctx, report):
        view = ctx.ensure_view()
        f32 = _np.dtype(_np.float32)
        shapes, dtypes = ctx.shapes, ctx.node_dtypes

        # -- seed variables ------------------------------------------------
        dyn_subst = {}
        for n in view.variables():
            shape = None
            if n.name in ctx.data_shapes and ctx.data_shapes[n.name]:
                shape = ctx.data_shapes[n.name]
            elif "__shape__" in n.attrs:
                shape = tuple(n.attrs["__shape__"])
            if shape is not None:
                conc, subst = self._concretize(ctx, shape)
                if subst:
                    dyn_subst[n.name] = (shape, conc)
                shapes[(id(n), 0)] = conc
            if n.name in ctx.dtypes:
                want = _np.dtype(ctx.dtypes[n.name])
                dtypes[(id(n), 0)] = want
                declared = n.attrs.get("__dtype__")
                if declared is not None and _np.dtype(declared) != want:
                    report.add(Diagnostic(
                        Severity.WARNING, self.name,
                        "dtype %s requested for %r, but the variable "
                        "declares __dtype__=%s" % (want, n.name, declared),
                        node=n.name))
            elif "__dtype__" in n.attrs:
                dtypes[(id(n), 0)] = _np.dtype(n.attrs["__dtype__"])
        for name, (orig, conc) in dyn_subst.items():
            report.add(Diagnostic(
                Severity.INFO, self.name,
                "dynamic dims in %r abstracted %s -> %s for "
                "interpretation" % (name, _fmt_shape(orig),
                                    _fmt_shape(conc)), node=name))

        # -- forward fixed point ------------------------------------------
        failed = set()      # nodes already diagnosed: report each once
        inferred = {}       # (op, attrs, input shapes, dtypes) -> outputs
        max_passes = max(3, len(view.topo))
        for _ in range(max_passes):
            progressed = False
            for n in view.topo:
                if n.op is None or id(n) in failed:
                    continue
                if all((id(n), i) in shapes
                       for i in range(self._nout(n))):
                    continue
                try:
                    attrs = n.op.normalize(n.attrs)
                except Exception:
                    failed.add(id(n))   # verifier already reported this
                    continue
                in_keys = [(id(i), ix) for (i, ix) in n.inputs]
                in_shapes = [shapes.get(k) for k in in_keys]
                in_dtypes = [dtypes.get(k, f32) for k in in_keys]
                if n.op.fill_shapes is not None:
                    try:
                        filled = list(n.op.fill_shapes(attrs,
                                                       list(in_shapes)))
                    except Exception as e:
                        self._fail(ctx, report, failed, n, in_shapes, e,
                                   stage="parameter shape completion")
                        continue
                    for k, s_old, s_new in zip(in_keys, in_shapes, filled):
                        if s_old is None and s_new is not None:
                            shapes[k] = tuple(s_new)
                            progressed = True
                    in_shapes = [shapes.get(k) for k in in_keys]
                if any(s is None for s in in_shapes):
                    continue        # blocked; maybe a later sweep fills it
                # a graph of repeated layers asks the same question once
                # a layer (and tracing a Pallas kernel for its shape is
                # not free): an answer is kept for the rest of this run
                sig = (n.op.name, n.op._freeze(attrs, ctx.training),
                       tuple(map(tuple, in_shapes)),
                       tuple(str(d) for d in in_dtypes))
                try:
                    out = inferred.get(sig)
                except TypeError:       # an attribute that does not hash
                    sig = out = None
                if out is None:
                    try:
                        out = self._eval_shape(ctx, n, attrs, in_shapes,
                                               in_dtypes)
                    except Exception as e:
                        self._fail(ctx, report, failed, n, in_shapes, e)
                        continue
                    if sig is not None:
                        inferred[sig] = out
                for i, o in enumerate(out):
                    shapes[(id(n), i)] = tuple(o.shape)
                    dtypes[(id(n), i)] = _np.dtype(o.dtype)
                progressed = True
            if not progressed:
                break

        # -- anything still unresolved? -----------------------------------
        self._report_blocked(ctx, report, view, shapes, failed)

    # ------------------------------------------------------------------
    @staticmethod
    def _eval_shape(ctx, n, attrs, in_shapes, in_dtypes):
        import jax
        structs = [jax.ShapeDtypeStruct(tuple(s), d)
                   for s, d in zip(in_shapes, in_dtypes)]
        if n.op.stochastic:
            key = jax.ShapeDtypeStruct((2,), _np.uint32)
            return jax.eval_shape(
                lambda k, *ins: n.op.bound(attrs, ctx.training)(
                    jax.random.wrap_key_data(k), *ins),
                key, *structs)
        return jax.eval_shape(n.op.bound(attrs, ctx.training), *structs)

    @staticmethod
    def _nout(n):
        try:
            return n.num_outputs()
        except Exception:
            return 1

    def _concretize(self, ctx, shape):
        """Replace dynamic (0/None) dims with a representative size."""
        conc, subst = [], False
        for ax, d in enumerate(shape):
            if d in (0, None):
                subst = True
                rep = _REPR_DYN
                if ctx.policy is not None and ctx.policy.seq_buckets:
                    rep = ctx.policy.seq_buckets[0]
                conc.append(rep)
            else:
                conc.append(int(d))
        return tuple(conc), subst

    def _fail(self, ctx, report, failed, n, in_shapes, err,
              stage="shape inference"):
        failed.add(id(n))
        view = ctx.view
        try:
            names = n.op.input_names(dict(n.attrs),
                                     num_inputs=len(n.inputs))
        except Exception:
            names = []
        if len(names) != len(n.inputs):
            names = [inp.name for (inp, _) in n.inputs]
        ins = ", ".join("%s=%s" % (nm, _fmt_shape(s))
                        for nm, s in zip(names, in_shapes))
        msg = str(err).strip().split("\n")[0]
        report.add(Diagnostic(
            Severity.ERROR, self.name,
            "%s failed: %s; inputs: %s" % (stage, msg, ins),
            node=n.name, op=n.op.name, provenance=view.provenance(n)))

    def _report_blocked(self, ctx, report, view, shapes, failed):
        """Name the FIRST node (topo order) whose output shapes stayed
        unknown without an error of its own — it is blocked on unknown
        inputs, and saying *which* is the actionable part."""
        for n in view.topo:
            if n.op is None or id(n) in failed:
                continue
            if all((id(n), i) in shapes for i in range(self._nout(n))):
                continue
            unknown = [inp.name for (inp, ix) in n.inputs
                       if (id(inp), ix) not in shapes]
            report.add(Diagnostic(
                Severity.WARNING, self.name,
                "shapes unresolved: blocked waiting on input(s) %s — "
                "provide shapes for the unshaped graph inputs"
                % unknown, node=n.name, op=n.op.name,
                provenance=view.provenance(n)))
            return


def node_inputs(symbol, op_name, data_shapes, dtypes=None):
    """``(attrs, input shapes, input dtypes)`` of every ``op_name`` node
    of ``symbol`` at these data shapes, in graph order: what such a node
    can observe of its inputs when the program is built (the shapes pass
    over the graph; a node whose inputs stayed unresolved is left
    out)."""
    from .core import analyze
    _report, ctx = analyze(symbol, data_shapes=dict(data_shapes),
                           dtypes=dict(dtypes or {}), passes=("shapes",))
    out = []
    for node in ctx.ensure_view().op_nodes():
        if node.op.name != op_name:
            continue
        keys = [(id(i), ix) for (i, ix) in node.inputs]
        if all(k in ctx.shapes and k in ctx.node_dtypes for k in keys):
            out.append((node.op.normalize(node.attrs),
                        [ctx.shapes[k] for k in keys],
                        [ctx.node_dtypes[k] for k in keys]))
    return out
