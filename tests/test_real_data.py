"""Real-data loaders (VERDICT r4 #7): Criteo-format files feed DLRM
end-to-end through the native prefetcher, matching the reference's
dataset pipeline (``examples/cpp/DLRM/dlrm.cc:315-420`` +
``preprocess_hdf.py``)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from flexflow_tpu.models.dlrm_data import load_criteo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def criteo_files(tmp_path_factory):
    """One tiny dataset written in all three pipeline stages: raw TSV,
    preprocess-input .npz, preprocessed .h5."""
    tmp = tmp_path_factory.mktemp("criteo")
    rng = np.random.default_rng(0)
    n = 192
    x_int = rng.integers(0, 100, size=(n, 13)).astype(np.float32)
    x_cat = rng.integers(0, 10**6, size=(n, 26)).astype(np.int64)
    y = rng.integers(0, 2, size=(n,)).astype(np.float32)
    np.savez(tmp / "d.npz", X_int=x_int, X_cat=x_cat, y=y)
    h5py = pytest.importorskip("h5py")
    with h5py.File(tmp / "d.h5", "w") as f:
        f.create_dataset("X_int", data=np.log(x_int + 1))  # preprocess_hdf
        f.create_dataset("X_cat", data=x_cat)
        f.create_dataset("y", data=y)
    with open(tmp / "d.tsv", "w") as f:
        for i in range(n):
            ints = "\t".join(
                str(int(v)) if i % 7 else "" for v in x_int[i]
            )  # every 7th row has missing dense fields
            cats = "\t".join(format(int(v), "x") for v in x_cat[i])
            f.write(f"{int(y[i])}\t{ints}\t{cats}\n")
    return tmp, x_int, x_cat, y


def test_h5_and_npz_agree(criteo_files):
    tmp, x_int, x_cat, y = criteo_files
    xs_h5, y_h5 = load_criteo(str(tmp / "d.h5"), vocab_sizes=1024)
    xs_np, y_np = load_criteo(str(tmp / "d.npz"), vocab_sizes=1024)
    assert len(xs_h5) == len(xs_np) == 27  # 26 tables + dense
    for a, b in zip(xs_h5, xs_np):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(y_h5, y_np)
    # dense got the reference log(x+1) transform
    np.testing.assert_allclose(
        xs_np[-1], np.log(x_int + 1), rtol=1e-5
    )
    # categorical ids reduced into the table vocabulary
    for t in xs_np[:-1]:
        assert t.dtype == np.int32 and t.shape == (192, 1)
        assert t.min() >= 0 and t.max() < 1024


def test_tsv_parses_missing_fields_and_hex(criteo_files):
    tmp, x_int, x_cat, y = criteo_files
    xs, yt = load_criteo(str(tmp / "d.tsv"), vocab_sizes=1024)
    assert len(xs) == 27 and len(yt) == 192
    # hex categoricals hash consistently with the int source
    np.testing.assert_array_equal(
        xs[0][:, 0], (x_cat[:, 0] % 1024).astype(np.int32)
    )
    # rows with blanked dense fields read as 0 -> log1p(0) == 0
    assert np.all(xs[-1][0] == 0.0)
    np.testing.assert_allclose(xs[-1][1], np.log(x_int[1] + 1), rtol=1e-5)
    np.testing.assert_array_equal(yt[:, 0], y)


def test_max_samples_truncates(criteo_files):
    tmp, *_ = criteo_files
    xs, y = load_criteo(str(tmp / "d.npz"), vocab_sizes=64, max_samples=50)
    assert len(y) == 50 and all(len(a) == 50 for a in xs)


def test_unknown_extension_rejected(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("x")
    with pytest.raises(ValueError, match="unrecognized"):
        load_criteo(str(p))


def test_dlrm_example_trains_from_disk(criteo_files):
    """examples/dlrm/dlrm.py --data <file> trains from disk; batches go
    through native/ffdl.cc when built (FFModel.fit routes there)."""
    tmp, *_ = criteo_files
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "examples", "dlrm", "dlrm.py"),
            "-b", "64", "-e", "1", "--data", str(tmp / "d.h5"),
            "--embedding-size", "512", "--sparse-feature-size", "8",
        ],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    assert "loaded" in r.stdout and "26 tables" in r.stdout
    assert "throughput:" in r.stdout


def test_fit_uses_native_prefetcher_when_available():
    """The native loader builds and yields batches when its build exists.
    ``fit`` routes shuffled epochs through ``native/ffdl.cc``; unshuffled
    ones, the --data example's among them, are fed as views of the
    dataset (``dataloader.ViewIterator``) and touch no loader."""
    from flexflow_tpu.runtime.native import native_available

    if not native_available():
        pytest.skip("native loader not built in this environment")
    from flexflow_tpu.runtime.native import NativeBatchIterator

    xs = [np.arange(32, dtype=np.float32).reshape(16, 2)]
    it = NativeBatchIterator(xs + [np.zeros((16, 1), np.int32)], 8)
    it.reset()  # arms the producer thread (fit calls this per epoch)
    batches = list(it)
    assert len(batches) == 2 and batches[0][0].shape == (8, 2)
