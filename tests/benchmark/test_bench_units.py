"""The benchmark's own arithmetic: work from shapes, the traffic copy,
the trace reduction, the data files.  CPU only, no chip, seconds."""

import glob
import json
import os

import types

import numpy as np
import pytest

from bench_fixtures import REPO, TINY_BERT, TINY_TRAIN_CELL, TINY_TRAIN_MIX, tmp_checkout, load_run_module

from benchmarks import readers
from benchmarks import trace_reduce as TR
from benchmarks import traffic as T
from benchmarks import work
from benchmarks.jobs import serve

BENCH = os.path.join(REPO, "benchmarks")


# ------------------------------------------------------------------ work.py
def test_encoder_flops_hand_counted():
    # b=2 s=4 h=8 ff=16 L=1 classes=3, forward by hand:
    #   q,k,v,o: 4 matmuls of (8 tokens x 8) @ (8 x 8)      = 4 * 2*8*8*8   = 4096
    #   ffn: (8x8)@(8x16) + (8x16)@(16x8)                   = 2 * 2*8*8*16  = 4096
    #   scores + p.v: per batch 2 * 2*s*s*h = 2*2*4*4*8=512, x2 batches     = 1024
    #   head: 2 * b * h * classes                           = 2*2*8*3       = 96
    fwd = 4096 + 4096 + 1024 + 96
    got = work.encoder_train_flops_per_step(
        batch=2, seq=4, hidden=8, ff_dim=16, num_layers=1, num_classes=3,
    )
    assert got == 3 * fwd


def test_bert_base_flops_per_token_matches_the_issue():
    # ISSUE 25: 567 MFLOP a token = 6 * 85.1 M + 12 * L * s * h
    got = work.encoder_train_flops_per_step(
        batch=16, seq=512, hidden=768, ff_dim=3072, num_layers=12, num_classes=64,
    ) / (16 * 512)
    assert abs(got - 567e6) / 567e6 < 0.01


def test_served_request_work_hand_counted():
    # prompt 5, chunk 4: chunks [0,4) and [4,5).  Chunk 1 reads 4 keys,
    # rows see 1+2+3+4 = 10; chunk 2 reads 5 keys, its row sees 5.
    # 3 new tokens: the first from prefill, two decode steps at positions
    # 5 and 6 reading 6 and 7 keys.
    w = work.served_request_work(prompt_len=5, prefill_pos=5, new_tokens=3, prefill_chunk=4)
    assert w["kv_token_reads"] == 4 + 5 + 6 + 7
    assert w["attended_pairs"] == 10 + 5 + 6 + 7
    assert w["positions"] == 5 + 2
    assert w["logit_rows"] == 3
    half = work.served_request_work(prompt_len=5, prefill_pos=4, new_tokens=0, prefill_chunk=4)
    assert half["kv_token_reads"] == 4 and half["logit_rows"] == 0


def test_paged_bytes_and_roofline():
    # 10 key/value token reads, 3 query rows, 2 heads of 4, bf16:
    # K and V: 2 * 10 * 8 * 2 = 320; q in and out: 2 * 3 * 8 * 2 = 96
    assert work.paged_attention_bytes(
        kv_token_reads=10, q_rows=3, heads=2, head_dim=4, kv_itemsize=2, q_itemsize=2,
    ) == 416
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(100.0, 50.0, peaks) == (5.0, "bandwidth")
    assert work.roofline_seconds(1000.0, 50.0, peaks) == (10.0, "compute")


# --------------------------------------------------------------- traffic.py
@pytest.mark.parametrize("kw", [
    dict(n_requests=12, seed=3, rate_rps=0.0),
    dict(n_requests=12, seed=4, rate_rps=25.0),
    dict(n_requests=12, seed=5, rate_rps=25.0, burst_factor=4.0),
    dict(n_requests=12, seed=6, rate_rps=9.0, tenants=3, shared_prefix=5,
         interactive_frac=0.4, session_turns=2),
])
def test_legacy_traffic_equals_the_programs_generator(kw):
    from flexflow_tpu.serve import traffic as P

    kw = dict(kw, prompt_len=(4, 12), max_new=(4, 24), vocab=256)
    mine = T.generate(T.TrafficSpec(**kw))
    theirs = P.synthetic_requests(P.TrafficSpec(**kw))
    assert len(mine) == len(theirs) == 12
    for a, b in zip(mine, theirs):
        assert a.id == b.id and a.arrival_s == b.arrival_s
        assert a.max_new_tokens == b.max_new_tokens
        assert a.prompt.tobytes() == b.prompt.tobytes()
        assert (a.tenant, a.tier, a.session) == (b.tenant, b.tier, b.session)


def test_fixed_set_gives_every_seed_the_same_work():
    mix = json.load(open(os.path.join(BENCH, "traffic_mixes", "serve_steady.json")))
    def lens(seed):
        spec = T.spec_from_cell(mix, seed=seed, seconds=100.0, vocab=50257)
        reqs = T.generate(spec)
        blk = reqs[:mix["block"]]
        return sorted((len(r.prompt), r.max_new_tokens) for r in blk), reqs
    a, ra = lens(1)
    b, rb = lens(2 ** 31 + 7)  # seeds go past 32 signed bits
    assert a == b
    assert [len(r.prompt) for r in ra[:20]] != [len(r.prompt) for r in rb[:20]]
    again = T.generate(T.spec_from_cell(mix, seed=1, seconds=100.0, vocab=50257))
    assert all(x.prompt.tobytes() == y.prompt.tobytes() and x.arrival_s == y.arrival_s
               for x, y in zip(ra, again))
    # arrivals cover the window at the stated rate: a block's mean gap is 1/rate
    assert abs(ra[mix["block"] - 1].arrival_s - mix["block"] / mix["rate_rps"]) < 1e-6
    assert all(r.arrival_s <= 100.0 + 1e-6 for r in ra)
    assert len(ra) == len(rb) == 4 * mix["block"]  # 100 s = four whole blocks


def test_backlog_is_sized_from_the_window():
    mix = json.load(open(os.path.join(BENCH, "traffic_mixes", "serve_saturated.json")))
    spec = T.spec_from_cell(mix, seed=9, seconds=10.0, vocab=50257)
    reqs = T.generate(spec)
    assert len(reqs) == mix["backlog_min"] + 10 * mix["backlog_requests_per_s"]
    assert all(r.arrival_s == 0.0 for r in reqs)
    lo, hi = mix["prompt_len"]
    assert all(lo <= len(r.prompt) <= hi for r in reqs)


# ---------------------------------------------------------- trace_reduce.py
def test_union_and_leaves():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)]
    assert TR.union_seconds(evs) == pytest.approx(2.5)
    nested = [("while", 0.0, 10.0), ("x", 1.0, 2.0), ("y", 4.0, 2.0), ("z", 20.0, 1.0)]
    assert [e[0] for e in TR.leaves(nested)] == ["x", "y", "z"]


def test_recorded_trace_of_the_training_step():
    """The first 0.13 s (two steps) of a real ``--trace 1`` run of
    ``bert_base.train_b16_s512`` on a v5e (PR 25), as the chip wrote it."""
    trace = TR.load_recorded(os.path.join(REPO, "tests", "benchmark", "recorded_train_trace.json.gz"))
    assert TR.device_planes(trace) == ["/device:TPU:0"]
    busy = TR.busy_seconds(trace)
    span = max(s + d for _, s, d in trace["/device:TPU:0"][TR.OPS_LINE])
    assert 0.9 * span < busy <= span  # the chip is busy nearly all of a step
    # three step programs start inside the record, two of them whole:
    # within those two the operations' union is the programs' time
    steps, n = TR.time_by_regex(trace, r"^jit_step", line=TR.MODULES_LINE)
    assert n == 3 and busy <= steps
    mods = sorted(e for e in trace["/device:TPU:0"][TR.MODULES_LINE] if e[0].startswith("jit_step"))
    whole = sorted(mods, key=lambda e: e[1])[:2]
    end = whole[1][1] + whole[1][2]
    inside = [e for e in trace["/device:TPU:0"][TR.OPS_LINE] if e[1] + e[2] <= end + 1e-9]
    assert TR.union_seconds(inside) == pytest.approx(whole[0][2] + whole[1][2], rel=0.02)
    # the two scanned loops (forward, backward) are containers, not work
    assert not any(name.startswith("while") for name, _ in TR.top_ops(trace, 50))
    fus, k = TR.time_by_regex(trace, r"^%\S*fusion")
    assert k > 100 and 0.5 * busy < fus <= busy


# ---------------------------------------- device time by program (XLA Modules)
# Two chips, the same programs on each: 3 prefill dispatches of 40, 50, 60 ms
# and 4 decode steps of 10 ms a chip; operations busy 0.16 s of a 0.25 s slice.
def _module_run(modules=True):
    plane = {
        TR.OPS_LINE: [("%fusion.1 = bf16[8]", 0.00, 0.10), ("%fusion.2 = bf16[8]", 0.12, 0.06)],
        TR.MODULES_LINE: [
            ("jit_prefill(123)", 0.00, 0.04), ("jit_decode(456)", 0.04, 0.01),
            ("jit_decode(456)", 0.05, 0.01), ("jit_prefill(123)", 0.06, 0.05),
            ("jit_decode(456)", 0.12, 0.01), ("jit_decode(456)", 0.13, 0.01),
            ("jit_prefill(123)", 0.14, 0.06), ("jit_convert_element_type(9)", 0.21, 0.001),
        ] if modules else [],
    }
    events = {"/device:TPU:0": plane, "/device:TPU:1": plane, "/host:CPU": {}}
    return types.SimpleNamespace(
        trace={"events": events, "busy_s": TR.busy_seconds(events), "window_s": 0.25},
        facts={}, peaks={}, chips=2)


@pytest.mark.parametrize("reader,regex,want", [
    ("module_time_share", "^jit_prefill", 100 * 0.15 / 0.16),
    ("module_time_share", "^jit_decode", 100 * 0.04 / 0.16),
    ("module_ms_per_call", "^jit_prefill", 50.0),
    ("module_ms_per_call", "^jit_decode", 10.0),
    ("module_ms_per_call", r"^jit_(decode|prefill)", 1e3 * 0.19 / 7),
])
def test_device_time_by_program_by_hand(reader, regex, want):
    run = _module_run()
    assert run.trace["busy_s"] == pytest.approx(0.16)
    assert getattr(readers, reader)(run, regex=regex) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("reader", ["module_time_share", "module_ms_per_call"])
def test_no_such_program_on_the_line_is_none(reader):
    fn = getattr(readers, reader)
    assert fn(_module_run(), regex="^jit_verify") is None  # never 0 for a program that did not run
    assert fn(_module_run(modules=False), regex="^jit_prefill") is None
    assert fn(types.SimpleNamespace(trace=None, facts={}), regex="^jit_prefill") is None


@pytest.mark.parametrize("calls,want", [(7, 1.0), (8, 7 / 8), (210, 7 / 210), (0, None)])
def test_traced_calls_share(calls, want):
    """Module events on the first chip over the calls the host counted: a
    device line that came back truncated (one call of thirty) shows."""
    events = _module_run().trace["events"]
    got = TR.traced_calls_share(events, serve.PROGRAMS, calls)
    assert got == (want if want is None else pytest.approx(want))
    assert TR.traced_calls_share({"/host:CPU": {}}, serve.PROGRAMS, 7) is None


def test_prefill_rows_valid_share_from_hand_made_requests():
    """Prompt positions prefilled over the rows the dispatches computed:
    a finished request counts its whole prompt, one cut mid-prompt what
    it got to, one never started nothing."""
    R = types.SimpleNamespace
    reqs = [R(prompt_len=100, tokens=[5, 6], prefill_pos=100),
            R(prompt_len=70, tokens=[], prefill_pos=64),
            R(prompt_len=30, tokens=[], prefill_pos=0)]
    facts = serve.prefill_rows(reqs, R(prefill_dispatches=4), {"slots": 4, "prefill_chunk": 32})
    assert facts == {"prefill_positions": 164, "prefill_rows_computed": 512}
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "prefill_rows_valid_share.tput.json")))
    run = types.SimpleNamespace(facts=facts, trace=None)
    assert getattr(readers, spec["reader"])(run, **spec["args"]) == pytest.approx(100 * 164 / 512)
    none = types.SimpleNamespace(facts=dict(facts, prefill_rows_computed=0), trace=None)
    assert getattr(readers, spec["reader"])(none, **spec["args"]) is None  # no dispatch, no share


def test_the_traced_slice_is_taken_without_the_python_frame_tracer(monkeypatch):
    import jax

    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: seen.update(dir=d, **kw))
    TR.start_trace("/somewhere")
    assert seen["dir"] == "/somewhere"
    assert seen["profiler_options"].python_tracer_level == 0
    assert seen["profiler_options"].host_tracer_level > 0  # TraceMe events carry the ff.* spans


# ------------------------------------------------------------ the data files
def _names(sub):
    return sorted(os.path.basename(p)[:-5] for p in glob.glob(os.path.join(BENCH, sub, "*.json")))


def test_every_file_loads_and_names_things_that_exist():
    mod = load_run_module(REPO)
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert [w["name"] for w in manifest["workloads"]] == [
        n for n in (w["name"] for w in manifest["workloads"]) if n in _names("workloads")
    ]
    for w in manifest["workloads"]:
        cell, config, metrics = mod.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert os.path.exists(os.path.join(BENCH, "jobs", cell["job"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "reference", config["family"] + ".py"))
        for name, unit in cell["end_to_end"].items():
            assert e2e[name]["unit"] == unit
            assert "workloads" not in e2e[name] or w["name"] in e2e[name]["workloads"]
        for name, spec in metrics.items():
            assert callable(mod.resolve_reader(spec["reader"]))
            m = per_layer[name]
            assert (m["unit"], m["layer"], m["moves"], m["source"], m["better"]) == (
                spec["unit"], spec["layer"], spec["moves"], spec["source"], spec["better"])
            assert w["name"] in m["workloads"]
    for c in manifest["configs"]:
        doc = json.load(open(os.path.join(REPO, c["file"])))
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        b, m = doc["builder_args"], doc["model"]
        widths = {v for v in m.values() if isinstance(v, int)}
        assert {b["hidden"], b["heads"], b["ff_dim"], b["num_layers"]} <= widths
    for name in _names("layer_metrics"):
        assert name in per_layer, f"{name} has a file and no entry in BENCHMARK.json"
    # ... and the other way round: an entry has a file, and each cell it names lists it
    cells = {w["name"]: mod.load_cell(w["name"])[0] for w in manifest["workloads"]}
    for name, m in per_layer.items():
        assert name in _names("layer_metrics"), f"{name} has an entry and no file"
        assert m["workloads"], name
        for c in m["workloads"]:
            assert name in cells[c]["layer_metrics"], f"{c} does not list {name}"


def test_retired_and_new_serve_metrics():
    """``pool_copy_share.*`` (could only read 0 since the pool turned
    position-major) is gone from files, cells and manifest alike;
    ``program_relayouts.*`` stands in all four serve cells, the
    program-time metrics and S1's valid-rows share in the backlog cells as
    ``.tput`` and in the steady cell as ``.lat``."""
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert not [n for n in list(per_layer) + _names("layer_metrics") if n.startswith("pool_copy_share")]
    backlog = {w["name"] for w in manifest["workloads"] if "serve_saturated" in w["traffic"]}
    steady = {"gpt2_small.serve_steady"}
    assert len(backlog) == 3
    for stem in ("prefill_device_share", "prefill_ms_per_dispatch", "decode_ms_per_step",
                 "prefill_rows_valid_share", "program_relayouts"):
        assert set(per_layer[f"{stem}.tput"]["workloads"]) == backlog, stem
        assert set(per_layer[f"{stem}.lat"]["workloads"]) == steady, stem
        assert per_layer[f"{stem}.tput"]["moves"] == "serve_tokens_per_s"
        assert per_layer[f"{stem}.lat"]["moves"] == "tpot_p95_ms"


def test_a_cell_config_reference_metric_and_reader_are_added_as_files(tmp_path):
    """No file of the benchmark is edited: new files resolve by name."""
    root = tmp_checkout(tmp_path, {
        "configs/tiny_bert.json": dict(TINY_BERT, family="dummy_family"),
        "workloads/tiny_bert.train.json": dict(
            TINY_TRAIN_CELL, layer_metrics=["dummy_metric", "init_params_s"]),
        "traffic_mixes/tiny_train.json": TINY_TRAIN_MIX,
        "layer_metrics/dummy_metric.json": {
            "layer": "dummy", "unit": "1", "better": "higher", "moves": "train_tokens_per_s",
            "source": "program_counter", "reader": "bench_dummy_readers:answer",
            "args": {"plus": 1}},
        "reference/dummy_family.py": "def param_shapes(cfg):\n    return {}\n",
    })
    with open(os.path.join(root, "bench_dummy_readers.py"), "w") as f:
        f.write("def answer(run, *, plus):\n    return run.facts['x'] + plus\n")
    import sys, types
    sys.path.insert(0, root)
    try:
        mod = load_run_module(root)
        cell, config, metrics = mod.load_cell("tiny_bert.train")
        assert cell["mix"]["batch"] == 8 and config["family"] == "dummy_family"
        reader = mod.resolve_reader(metrics["dummy_metric"]["reader"])
        assert reader(types.SimpleNamespace(facts={"x": 41}), plus=1) == 42
        assert os.path.exists(os.path.join(root, "benchmarks", "reference", "dummy_family.py"))
        # a cell that lists a metric whose end-to-end metric it does not report is refused
        bad = dict(TINY_TRAIN_CELL, layer_metrics=["serve_compile_s", "window_wall_ms.tput"])
        with open(os.path.join(root, "benchmarks", "workloads", "bad.json"), "w") as f:
            json.dump(bad, f)
        with pytest.raises(SystemExit):
            mod.load_cell("bad")
    finally:
        sys.path.remove(root)
        sys.modules.pop("bench_dummy_readers", None)


def test_no_tpu_no_number(capsys):
    """On a host without a TPU the command exits non-zero and prints no result."""
    mod = load_run_module(REPO)
    with pytest.raises(SystemExit) as e:
        mod.main(["--workload", "bert_base.train_b16_s512", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    import jax

    mod = load_run_module(REPO)

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit) as e:
        mod.device_or_exit(1)
    assert e.value.code not in (0, None)
    assert "peaks.json" in capsys.readouterr().err
