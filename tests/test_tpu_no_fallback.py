"""Nothing on the device paths may hide the device (ISSUE 21).

* an unknown TPU ``device_kind`` is an error, not a v5p;
* ``chip_smoke.py`` and ``bench.py`` refuse to run without a TPU;
* the persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
  says and nowhere else, for ``FFModel`` and for ``--serve``, and a second
  process is served from it.

The cache and refusal tests run children: the variable is read when jax is
imported, and this process pins the cache off (conftest.py).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _child(args, env_extra, cwd, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    return subprocess.run(
        [sys.executable, *args], env=env, cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
    )


def test_unknown_device_kind_raises():
    from flexflow_tpu.search.cost import TPUMachineModel

    with pytest.raises(ValueError, match="v9 imaginary"):
        TPUMachineModel.for_chip("TPU v9 imaginary")
    assert TPUMachineModel.for_chip("TPU v5 lite").source == "preset:v5 lite"

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(ValueError, match="v9 imaginary"):
        bench._peak_flops("TPU v9 imaginary")
    assert bench._peak_flops("TPU v5 lite") == 197e12


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_device_entry_points_refuse_to_run_without_a_tpu(script, tmp_path):
    r = _child([os.path.join(REPO, script)], {}, str(tmp_path), timeout=120)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    # no result: not one JSON object on stdout
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_chip_smoke_alone_prints_no_result(tmp_path):
    """In a directory that holds nothing else of the repo it exits
    non-zero before touching JAX and writes nothing to stdout."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path, timeout=60,
        capture_output=True, text=True,
    )
    assert r.returncode != 0
    assert r.stdout == ""
    assert "flexflow_tpu" in r.stderr


@pytest.mark.parametrize("fails", [False, True])
def test_chip_smoke_last_line_is_the_contract_object(fails, monkeypatch, capsys):
    """The last stdout line is exactly {"ok", "device": {"platform",
    "kind", "count"}} — on success, and with ok false and a non-zero
    exit when a phase failed; the phases' account is on the line before."""
    import jax

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)

    def run_phases(n_devices):
        cs.check(not fails, "a phase failed")
        return {"phases": {"kernels": {}}, "compile_cache": {}}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cs, "run_phases", run_phases)
    rc = cs.main()
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert (rc != 0) == fails
    assert set(last) == {"ok", "device"} and last["ok"] is (not fails)
    assert set(last["device"]) == {"platform", "kind", "count"}
    d = jax.devices()
    assert last["device"] == {
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    }
    if not fails:
        assert lines[-2].startswith("summary: ")
        assert lines[-2].endswith('"claim": null}')


_TRAIN = """
import numpy as np
from flexflow_tpu import ActiMode, FFConfig, FFModel
from flexflow_tpu.obs import configure
tracer = configure(level="step")
m = FFModel(FFConfig(batch_size=8))
t = m.create_tensor((8, 16))
t = m.dense(t, 32, ActiMode.RELU)
m.softmax(m.dense(t, 4))
m.compile(seed=0)
rng = np.random.default_rng(0)
x = rng.normal(size=(16, 16)).astype(np.float32)
y = rng.integers(0, 4, size=(16, 1)).astype(np.int32)
m.fit(x, y, epochs=1, verbose=False)
print("HITS", int(tracer.summary()["counters"].get("jit_cache.persistent_hit", 0)))
"""


def _entries(path):
    if not os.path.isdir(path):
        return set()
    return {f for f in os.listdir(path) if f.endswith("-cache")}


def test_compile_cache_lives_where_the_environment_says(tmp_path):
    from flexflow_tpu.config import DEFAULT_COMPILE_CACHE_DIR

    cache = str(tmp_path / "cache")
    default_before = _entries(DEFAULT_COMPILE_CACHE_DIR)
    env = {"JAX_COMPILATION_CACHE_DIR": cache}

    first = _child(["-c", _TRAIN], env, str(tmp_path))
    assert first.returncode == 0, first.stderr[-2000:]
    assert "HITS 0" in first.stdout
    trained = _entries(cache)
    assert trained, "FFModel.fit wrote nothing to JAX_COMPILATION_CACHE_DIR"

    # a second process compiles nothing new and says it was served
    second = _child(["-c", _TRAIN], env, str(tmp_path))
    assert second.returncode == 0, second.stderr[-2000:]
    assert "HITS 1" in second.stdout
    assert _entries(cache) == trained

    serve = _child(
        ["-m", "flexflow_tpu", "--serve", "--requests", "2"], env, str(tmp_path)
    )
    assert serve.returncode == 0, serve.stderr[-2000:]
    assert json.loads(serve.stdout.strip().splitlines()[-1])["requests_finished"] == 2
    assert _entries(cache) > trained, "--serve wrote nothing to the cache"

    # ... and nowhere else: not the in-checkout default, not the cwd
    assert _entries(DEFAULT_COMPILE_CACHE_DIR) == default_before
    assert sorted(os.listdir(tmp_path)) == ["cache"]
