"""``benchmarks/setup_readers.py``: the program's set-up tally read into
per-layer metrics, on a hand-made tally with hand-computed answers, None
where there is no ``ff.setup`` span, and through ``run.py`` itself in a
tiny cell that lists them (a copy of the benchmark: no file of it is
edited).  ``SETUP_METRICS`` are the five metric files a ``benchmark`` PR
would add under ``layer_metrics/`` (PERF.md §7)."""

import types

import pytest

import bench_fixtures as F

from benchmarks import setup_readers as SU

SETUP_METRICS = {
    "setup_unattributed_s": {"unit": "s", "reader": "setup_unattributed_s", "args": {}},
    "setup_lower_s": {"unit": "s", "reader": "setup_lower_s", "args": {}},
    "setup_compile_load_s": {"unit": "s", "reader": "setup_compile_load_s", "args": {}},
    "setup_lowerings": {"unit": "1", "reader": "setup_count", "args": {"field": "lowerings"}},
    "setup_cache_misses": {"unit": "1", "reader": "setup_count",
                           "args": {"field": "cache_misses"}},
}


def _spec(name):
    m = SETUP_METRICS[name]
    return {"layer": "entry points", "unit": m["unit"], "better": "lower", "moves": "setup_s",
            "source": "program_span", "reader": f"benchmarks.setup_readers:{m['reader']}",
            "args": m["args"]}


def _prog(traces, trace_s, lowerings, lower_s, compiles, compile_s, hits, misses):
    return {"traces": traces, "trace_s": trace_s, "lowerings": lowerings, "lower_s": lower_s,
            "compiles": compiles, "compile_s": compile_s, "cache_hits": hits,
            "cache_misses": misses}


TALLY = {
    "spans": {"model": {"count": 1, "seconds": 12.0}, "engine": {"count": 1, "seconds": 9.0},
              "serve_programs": {"count": 1, "seconds": 2.0},
              "warmup": {"count": 1, "seconds": 6.5}},
    "outer_s": 21.0,
    "programs": {
        "model": {"init_fn": _prog(200, 1.0, 200, 3.0, 200, 4.0, 200, 0)},
        "serve_programs": {"cast": _prog(1, 0.25, 1, 0.25, 1, 0.5, 0, 1)},
        "warmup": {"decode": _prog(2, 0.5, 3, 1.5, 3, 2.0, 1, 2),
                   "prefill": _prog(1, 0.25, 1, 0.75, 1, 1.5, 1, 0)},
    },
}


def _run(setup_s=33.5):
    return types.SimpleNamespace(facts={"setup_s": setup_s}, trace=None)


def _call(name, run):
    m = SETUP_METRICS[name]
    return getattr(SU, m["reader"])(run, **m["args"])


@pytest.mark.parametrize("metric,want", [
    ("setup_unattributed_s", 33.5 - 21.0),
    ("setup_lower_s", 1.0 + 3.0 + 0.25 + 0.25 + 0.5 + 1.5 + 0.25 + 0.75),
    ("setup_compile_load_s", 4.0 + 0.5 + 2.0 + 1.5),
    ("setup_lowerings", 205.0),
    ("setup_cache_misses", 3.0),
])
def test_setup_metrics_by_hand(monkeypatch, metric, want):
    monkeypatch.setattr(SU, "summary", lambda: TALLY)
    assert _call(metric, _run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(SETUP_METRICS))
def test_no_setup_span_is_none(monkeypatch, metric):
    """A tally with no span, and a program whose ``obs`` has no tally at
    all (the parent of the PR that brought it), read None and never raise."""
    from flexflow_tpu import obs

    from flexflow_tpu.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "_SETUP", trace_mod._SetupTally())
    assert SU.summary() is None and _call(metric, _run()) is None
    monkeypatch.delattr(obs, "setup_summary")
    assert _call(metric, _run()) is None


def test_unattributed_needs_setup_s(monkeypatch):
    monkeypatch.setattr(SU, "summary", lambda: TALLY)
    assert SU.setup_unattributed_s(types.SimpleNamespace(facts={})) is None


def test_a_traced_serve_run_prints_the_setup_metrics(tmp_path, monkeypatch, capsys):
    from flexflow_tpu.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "_SETUP", trace_mod._SetupTally())  # this run's alone
    names = sorted(SETUP_METRICS)
    cell = F.tiny_serve_cell("tiny_gpt.backlog", "tiny_backlog",
                             {"serve_tokens_per_s": "tokens/s"}, ["window_wall_ms.tput"] + names)
    root = F.tmp_checkout(tmp_path, {
        "configs/tiny_gpt.json": F.TINY_GPT, "workloads/tiny_gpt.backlog.json": cell,
        "traffic_mixes/tiny_backlog.json": F.TINY_BACKLOG_MIX,
        **{f"layer_metrics/{n}.json": _spec(n) for n in names},
    })
    rc, res, _ = F.run_main(root, ["--workload", "tiny_gpt.backlog", "--seed", "7",
                                   "--seconds", "2", "--trace", "1"], monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    got = {n: res["metrics"][n]["value"] for n in names}
    tally = SU.summary()
    assert set(tally["spans"]) == {"model", "engine", "serve_programs", "warmup"}
    # the outermost spans and what no span holds make up setup_s to the microsecond
    assert got["setup_unattributed_s"] + tally["outer_s"] == pytest.approx(
        res["facts"]["setup_s"], abs=1e-6)
    assert 0 < got["setup_unattributed_s"] < res["facts"]["setup_s"]
    assert got["setup_lower_s"] > 0 and got["setup_compile_load_s"] > 0
    # one init program a weight of the 4-layer decoder, then both serve programs
    init = tally["programs"]["model"]["init_fn"]["lowerings"]
    assert init >= 4 * 4 and got["setup_lowerings"] >= init + 2
    assert got["setup_cache_misses"] >= 0  # a count: the suite's cache may be on or off
