"""The LSTMP step graph the configuration builds against the plain
reference, logits for logits, at a tiny size on the CPU."""
import numpy as np

from benchmark import harness

CFG = {"vocab_size": 300, "embed_dim": 24, "num_layers": 2, "lstm_cells": 48,
       "proj_dim": 24}


def test_step_graph_matches_the_reference():
    import jax
    import mxnet_tpu as mx
    from jax import lax
    from mxnet_tpu.predict import Predictor
    conf = harness.load_module("configs", "biglstm-lm1b-f32")
    ref = harness.load_module("reference", "biglstm-lm1b-f32")
    params = conf.init_params(CFG, 3000000041)
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == conf.param_shapes(CFG)
    step, state_info = conf.build_step(CFG)
    assert [s["name"] for s in state_info] == [
        "lstm0_r", "lstm0_c", "lstm1_r", "lstm1_c"]
    n, length = 3, 6
    tokens = np.random.default_rng(5).integers(1, 300, (n, length))
    shapes = {"token": (n,)}
    shapes.update({s["name"]: (n,) + tuple(s["shape"]) for s in state_info})
    pred = Predictor(step,
                     {k: mx.nd.array(np.asarray(v))
                      for k, v in params.items()}, {}, shapes, ctx=mx.cpu())
    states = {s["name"]: np.zeros(shapes[s["name"]], np.float32)
              for s in state_info}
    got = []
    for t in range(length):
        pred.forward(token=tokens[:, t].astype(np.float32), **states)
        got.append(pred.get_output(0))
        states = {s["name"]: pred.get_output(1 + i)
                  for i, s in enumerate(state_info)}
    got = np.stack(got, axis=1)                        # (n, length, vocab)
    hi = lax.Precision.HIGHEST
    hidden = ref._hidden(params, CFG, jax.numpy.asarray(tokens, "int32"), hi)
    want = np.asarray(ref._head(params, hidden.reshape(n * length, -1), hi)
                      ).reshape(n, length, -1)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_altered_token_reads_a_gap_and_a_served_one_none():
    import jax.numpy as jnp
    from jax import lax
    conf = harness.load_module("configs", "biglstm-lm1b-f32")
    ref = harness.load_module("reference", "biglstm-lm1b-f32")
    params = conf.init_params(CFG, 11)
    prompt = [5, 17, 200]
    served = []
    seq = list(prompt)
    for _ in range(6):                       # greedy, by the reference
        r = ref._hidden(params, CFG, jnp.asarray([seq], "int32"),
                        lax.Precision.HIGHEST)[0, -1:]
        tok = int(jnp.argmax(ref._head(params, r, lax.Precision.HIGHEST)))
        served.append(tok)
        seq.append(tok)
    sound = ref.served_gaps(params, CFG, [(prompt, served)], width=16)
    assert sound["tokens"] == 6 and float(sound["gaps"].max()) == 0.0
    altered = list(served)
    altered[3] = (altered[3] + 1) % 300
    bad = ref.served_gaps(params, CFG, [(prompt, altered)], width=16)
    assert float(bad["gaps"][3]) > 0.5
    low = ref.served_gaps(params, CFG, [(prompt, served)], width=16,
                          precision="bfloat16")
    assert low["gaps"].shape == (6,)
