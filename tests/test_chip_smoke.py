"""chip_smoke.py on the CPU: it must refuse to pass here, and its phases
must pass their own checks at a tiny size when the test — never the
command line — tells them which platform to expect.  Also the
compile-cache placement helper the script shares with bench.py.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = {
    "train": dict(num_layers=18, image=32, num_classes=10, batch=8,
                  steps=5, steps4=3, lr=0.05, momentum=0.9),
    "decode": dict(vocab=64, layers=2, hidden=32, slots=4, max_len=64,
                   requests=6, requests4=8, prompt=(2, 6), new_tokens=4),
    "kv": dict(vocab=64, d=32, blocks=2, slots=4, max_len=64, requests=4,
               prompt=4, new_tokens=4),
}


def _cpu_child(code_or_script, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in drop:
        env.pop(k, None)
    return subprocess.run([sys.executable] + code_or_script, env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_backend():
    """As the driver runs it in the sandbox: non-zero exit, no result."""
    for argv in ([], ["--four-chips"]):
        out = _cpu_child([os.path.join(REPO, "chip_smoke.py")] + argv)
        assert out.returncode != 0, out.stdout
        assert out.stdout.strip() == "", out.stdout
        assert "tpu" in out.stderr


@pytest.mark.parametrize("phase", ["train", "decode", "kv", "train4",
                                   "serve4"])
def test_phase_rehearsal_at_tiny_size(phase, capsys):
    """Each phase passes its own checks on CPU devices (the four-chip
    ones on four of conftest's virtual devices) and prints one line."""
    cache = chip_smoke._CompileCache()
    getattr(chip_smoke, "phase_" + phase)(TINY, 0, "cpu", cache)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["phase"] == phase
    assert rec["compile_s"] >= 0 and rec["run_s"] > 0
    assert "CPU" in json.dumps(rec["param_devices"]).upper()


def test_failed_check_fails_the_run(monkeypatch, tmp_path, capsys):
    """A false check raises out of main(): no final line, no exit 0."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    never_falls = dict(TINY, train=dict(TINY["train"], lr=0.0))
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke.main([], sizes=never_falls, platform="cpu")
    assert '"ok"' not in capsys.readouterr().out


def test_main_prints_the_contract_line_last(monkeypatch, tmp_path, capsys):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main(["--seed", "3"], sizes=TINY,
                           platform="cpu") == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert [json.loads(ln)["phase"] for ln in lines[:-1]] \
        == ["start", "train", "decode", "kv"]
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper returns it and sets
    no directory in code."""
    import jax
    from mxnet_tpu import config
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert config.compile_cache_dir() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "c").exists()


def test_compile_cache_dir_defaults_to_one_fixed_path():
    """Unset, it is <checkout>/.jax_cache in every process — the path is
    part of the cache key, so it must not move — and JAX is pointed at
    it."""
    code = ("import jax; from mxnet_tpu import config; "
            "print(config.compile_cache_dir()); "
            "print(jax.config.jax_compilation_cache_dir)")
    seen = set()
    for _ in range(2):
        out = _cpu_child(["-c", code],
                         drop=("JAX_COMPILATION_CACHE_DIR",))
        assert out.returncode == 0, out.stderr
        seen.add(tuple(out.stdout.split()))
    want = os.path.join(REPO, ".jax_cache")
    assert seen == {(want, want)}
