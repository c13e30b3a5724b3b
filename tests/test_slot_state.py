"""serving/slot_state.py, the one reader of ``state_info``: pool shapes,
dtypes and bytes of a recurrent row, a whole cache and a window against
hand-written values; the step graph's input grid as the engine's
preflights, the optimizer and the goodput pricer each used to build it;
the keys of a speculative pool; the one dtype rule; a prefill laid into
rings and whole caches against NumPy; the prefill commit's AOT tag."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import smallthinker as st
from mxnet_tpu.serving import DecodeEngine, StepProgram
from mxnet_tpu.serving.slot_state import SlotLayout, SlotState
from mxnet_tpu.telemetry import goodput

from test_decode import _attn_step, _lstm_step
import test_smallthinker as ts

BF16 = np.dtype(jnp.bfloat16)
F32 = np.dtype(np.float32)
# layer 0 global (a whole cache of MAX_LEN rows), layer 1 a window of 8
CFG2 = dict(ts.CFG, num_hidden_layers=2, rope_layout=[0, 1],
            sliding_window_layout=[0, 1])


def _smallthinker_layout(num_slots=4):
    return SlotLayout(st.state_info(CFG2, ts.MAX_LEN), num_slots, BF16)


def _lstm_layout(num_slots=4):
    return SlotLayout(_lstm_step()[2], num_slots, np.float32)


@pytest.mark.parametrize("build,index,state,pool_bytes", [
    (_lstm_layout, 1, SlotState("c", "c", (16,), F32, False, 0),
     2 * 4 * 16 * 4),
    (_smallthinker_layout, 0,
     SlotState("l0_k", "l0_k", (48, 16), BF16, True, 0),
     2 * (4 * 48 * 16 * 2) + 2 * (4 * 8 * 16 * 2)),
    (_smallthinker_layout, 3,
     SlotState("l1_v", "l1_v", (8, 16), BF16, True, 8),
     2 * (4 * 48 * 16 * 2) + 2 * (4 * 8 * 16 * 2)),
], ids=["recurrent_row", "whole_cache", "window"])
def test_pool_shapes_dtypes_and_bytes(build, index, state, pool_bytes):
    lay = build()
    assert lay.target[index] == state and lay.draft == ()
    assert lay.pool_shape(state) == (4,) + state.row
    assert lay.pool_bytes() == pool_bytes
    assert lay.slot_bytes() == pool_bytes // 4
    (_s, row), = [p for p in lay.zeros() if p[0] == state]
    (_s, pool), = [p for p in lay.zeros(pool=True, xp=jnp) if p[0] == state]
    assert row.shape == state.row and row.dtype == state.dtype
    assert pool.shape == (4,) + state.row and pool.dtype == state.dtype
    assert not row.any() and not np.asarray(pool, np.float32).any()
    # a join zeroes a recurrent row and leaves a cache alone
    assert (state.name in lay.reset_names()) == (not state.cache)
    assert (state.key in dict(lay.cache_rows())) == state.cache


def test_pool_bytes_divide_along_the_plans_state_rules():
    lay = _smallthinker_layout()
    # the feature axis of the window states over two devices, the whole
    # caches replicated; 3 does not divide 16 and falls back to whole
    spec = {"axes": {"tp": 2}, "state_rules": [["^l1_", [None, None, "tp"]]]}
    assert lay.pool_bytes(spec) == 2 * 6144 + 2 * 1024 // 2
    assert lay.slot_bytes(spec) == lay.pool_bytes(spec) // 4
    odd = {"axes": {"tp": 3}, "state_rules": [[".*", [None, None, "tp"]]]}
    assert lay.pool_bytes(odd) == lay.pool_bytes()


def _spec_layout():
    (_t, _tp, tinfo), (_d, _dp, dinfo) = _attn_step(), _attn_step(seed=1)
    for i in tinfo + dinfo:
        i["cache"] = True
    dinfo[1]["dtype"] = "float16"
    return SlotLayout(tinfo, 3, BF16, dinfo)


@pytest.mark.parametrize("which,keys,names", [
    ("target", ["k_cache", "v_cache"], ["k_cache", "v_cache"]),
    ("draft", ["draft:k_cache", "draft:v_cache"], ["k_cache", "v_cache"]),
    ("all", ["k_cache", "v_cache", "draft:k_cache", "draft:v_cache"],
     ["k_cache", "v_cache"] * 2),
])
def test_keys_of_a_speculative_pool(which, keys, names):
    lay = _spec_layout()
    states = lay.states(which)
    assert [s.key for s in states] == keys
    assert [s.name for s in states] == names
    assert [k for k, _t in lay.cache_rows(which)] == keys
    assert [str(s.dtype) for s in lay.states("all")] == \
        ["bfloat16", "bfloat16", "bfloat16", "float16"]
    # the draft's pool is not the target's: the memory preflight holds
    # the target's resident under a prefill
    assert lay.pool_bytes() == 2 * 3 * 16 * 8 * 2
    assert lay.pool_bytes(which="draft") == lay.pool_bytes()


@pytest.mark.parametrize("which", ["target", "draft"])
def test_grid_of_a_step_graph(which):
    """What ``_preflight``, ``price_step``, ``logits_shape``,
    ``_optimize_step`` and ``_price_step_sym`` each built by hand (the
    fixture takes ``pos`` and no ``valid``)."""
    step = _attn_step(seed=which == "draft")[0]
    grid = _spec_layout().grid(step, "token", "pos", "valid", which)
    want = {"token": (3,), "k_cache": (3, 16, 8), "v_cache": (3, 16, 8),
            "pos": (3,)}
    assert grid.shapes == want and list(grid.shapes) == list(want)
    v_dtype = np.dtype("float16") if which == "draft" else BF16
    assert grid.dtypes == {"token": F32, "pos": F32, "k_cache": BF16,
                           "v_cache": v_dtype}
    assert grid.state_names == ["k_cache", "v_cache"]
    assert grid.donate == {"k_cache": 1, "v_cache": 2}


def test_grid_leaves_out_vectors_the_graph_does_not_take():
    step = _lstm_step()[0]
    grid = _lstm_layout().grid(step, "token", "pos", "valid")
    assert grid.shapes == {"token": (4,), "h": (4, 16), "c": (4, 16)}
    assert set(grid.dtypes.values()) == {F32}
    assert grid.donate == {"h": 1, "c": 2}
    # a program without the input names none (``StepProgram.pos_name``)
    assert _lstm_layout().grid(step, "token", None, None).shapes \
        == grid.shapes


def test_dtype_none_takes_the_engines_dtype_everywhere():
    """``"dtype": None`` is "not given", for the pool, the preflights
    and the goodput price alike (goodput once read it as a dtype)."""
    step, params, info = _attn_step()
    params = {k: v.astype("bfloat16") for k, v in params.items()}
    none = [dict(i, dtype=None) for i in info]
    assert SlotLayout(none, 2, BF16).target == SlotLayout(info, 2, BF16).target
    engines = [DecodeEngine(step, params, {}, i, num_slots=2, max_len=16,
                            dtype=jnp.bfloat16, start=False)
               for i in (none, info)]
    try:
        progs = [e._replicas[0].program for e in engines]
        for prog in progs:
            assert {str(v.dtype) for v in prog.init_states().values()} \
                == {"bfloat16"}
        prices = [goodput.price_step_program(p) for p in progs]
        assert prices[0] == prices[1] and prices[0] > 0
        assert engines[0].memory_plan["digest"] \
            == engines[1].memory_plan["digest"]
        assert engines[0].memory_plan["pool_bytes"] == 2 * 2 * 16 * 8 * 2
    finally:
        for e in engines:
            e.close()


def _ring(rows, length, window):
    """Row ``j`` of a ring: the last position ``p < length`` with
    ``p mod window == j`` (None where the prompt never reached ``j``)."""
    out = {}
    for p in range(length):
        out[p % window] = rows[p]
    return out


@pytest.mark.parametrize("length", [5, 8, 19],
                         ids=["shorter", "equal", "longer"])
def test_lay_prefill_against_a_numpy_ring(length):
    """One padded prefill of 24 positions into a recurrent row, a whole
    cache of 32 rows and a window of 8, beside a dead batch row that is
    given row 0's slot and is overwritten by it."""
    info = [{"name": "h", "shape": (3,)},
            {"name": "k", "shape": (32, 2), "cache": True},
            {"name": "w", "shape": (8, 2), "cache": True, "window": 8}]
    lay = SlotLayout(info, 4, np.float32)
    rng = np.random.default_rng(length)
    bufs = [rng.standard_normal(lay.pool_shape(s)).astype(np.float32)
            for s in lay.target]
    rows = [rng.standard_normal((2, 3)).astype(np.float32),
            rng.standard_normal((2, 24, 2)).astype(np.float32),
            rng.standard_normal((2, 24, 2)).astype(np.float32)]
    slots, lens = [2, 2], [length, length]      # batch row 1 is dead
    h, k, w = [np.asarray(b) for b in lay.lay_prefill(
        [jnp.asarray(b) for b in bufs], [jnp.asarray(r) for r in rows],
        jnp.asarray(slots, jnp.int32), jnp.asarray(lens, jnp.int32))]
    for got, before in zip((h, k, w), bufs):    # other slots untouched
        assert np.array_equal(got[[0, 1, 3]], before[[0, 1, 3]])
    assert np.array_equal(h[2], rows[0][0])
    # a whole cache: position p in row p, rows past the bucket kept
    assert np.array_equal(k[2, :24], rows[1][0])
    assert np.array_equal(k[2, 24:], bufs[1][2, 24:])
    want = _ring(rows[2][0], length, 8)
    assert len(want) == min(length, 8)
    for j, row in want.items():
        assert np.array_equal(w[2, j], row), j


def test_lay_prefill_refuses_rows_that_fit_no_state():
    lay = SlotLayout([{"name": "h", "shape": (3,)}], 2, np.float32)
    with pytest.raises(MXNetError, match="fit neither state 'h'"):
        lay.lay_prefill([jnp.zeros((2, 3))], [jnp.zeros((1, 5, 3))],
                        jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("build,tag", [
    (_lstm_layout, "lay_rows_v1|0:0,0:0"),
    (_smallthinker_layout, "lay_rows_v1|1:0,1:0,1:8,1:8"),
], ids=["lstm", "smallthinker"])
def test_commit_tag_is_the_parents(build, tag):
    """A warm AOT cache from before this module still hits."""
    assert build().commit_tag() == tag


def test_what_a_declaration_may_not_say():
    with pytest.raises(MXNetError, match="window of 8 rows but is no cache"):
        SlotLayout([{"name": "h", "shape": (8, 2), "window": 8}], 2,
                   np.float32)
    # a speculative commit writes a cache at ``pos``
    lay = _spec_layout()
    assert lay.cache_rows("draft", "pos", has_pos=True) \
        == [("draft:k_cache", 16), ("draft:v_cache", 16)]
    with pytest.raises(MXNetError, match="draft state 'k_cache' is "
                       "cache-declared .* no 'at' input"):
        lay.cache_rows("draft", "at", has_pos=False)
    step, params, info = _lstm_step()
    assert StepProgram(step, params, {}, info, 2).layout.cache_rows() == []
