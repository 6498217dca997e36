"""CPU tests of the on-chip benchmark's yardstick: trace reduction, counts,
seeded schedules, the data-driven lookup, and the refusal to run off a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on sys.path)
import counts
import harness
import tracing


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def recorded_trace():
    """A small trace in the profiler's layout: a host plane with the window
    and loop spans, one TPU plane with ops and modules."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tracing.WINDOW_SPAN, 0, 1000),
        ev("job_step", 0, 400), ev("on_step", 400, 200),
        ev("job_step", 600, 400)])])
    ops = NS(name="XLA Ops", events=[
        ev("fusion.1", 10, 290), ev("_best_kernel", 300, 50),
        ev("fusion.1", 620, 300), ev("fusion.2", 700, 100),  # overlaps
        ev("fusion.1", 990, 50)])  # runs past the window's end
    modules = NS(name="XLA Modules", events=[ev("jit_step", 10, 340),
                                             ev("jit_step", 620, 400)])
    dev = NS(name="/device:TPU:0", lines=[ops, modules])
    other = NS(name="/device:TPU:0 SparseCore", lines=[ops])
    return NS(planes=[host, dev, other])


def test_trace_reduction_on_a_recorded_trace():
    t = tracing.reduce_profile(recorded_trace(), chips=1,
                               span_names=("job_step", "on_step"))
    assert t.window_s == pytest.approx(1e-3)
    # busy: [10,350) + [620,920) + [990,1000) = 340 + 300 + 10 us
    assert t.busy_s == pytest.approx(650e-6)
    assert t.idle_share == pytest.approx(0.35)
    assert t.seconds_matching(r"_best_kernel") == pytest.approx(50e-6)
    assert t.op_seconds["fusion.1"] == pytest.approx(600e-6)
    # gaps: [350,620) mostly under on_step, [920,990) under job_step; the
    # 10 us gap [0,10) is shorter than MIN_GAP_S and is not charged
    assert t.idle_gaps["on_step"] == pytest.approx(270e-6)
    assert t.idle_gaps["job_step"] == pytest.approx(70e-6)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(600e-6)]
    assert len(b["idle_gaps"]) <= 10


def test_trace_without_window_span_is_refused():
    pd = recorded_trace()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench_window"):
        tracing.reduce_profile(pd, chips=1, span_names=())


def test_param_count_against_hand_sums():
    gpt2 = json.loads((tiny.HERE / "configs" / "gpt2-124m.json").read_text())
    # 12 x (4 x 768^2 attention + 2 x 768 x 3072 MLP + 2 LayerNorms x 2 x 768)
    # + final LayerNorm 2 x 768 + tied embedding 50304 x 768
    hand = 12 * (4 * 768 ** 2 + 2 * 768 * 3072 + 4 * 768) + 2 * 768 \
        + 50304 * 768
    assert counts.param_count(gpt2["sizes"]) == hand == 123_606_528
    # a SwiGLU decoder without norm parameters, untied: 3 FFN matrices
    olmo = dict(gpt2["sizes"], n_layers=16, d_model=2048, head_dim=128,
                n_heads=16, n_kv_heads=16, d_ff=8192, glu=True,
                norm="layernorm_np", tie_embeddings=False)
    hand = 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192) + 2 * 50304 * 2048
    assert counts.param_count(olmo) == hand
    assert counts.train_flops_per_token(gpt2["sizes"]) == 6 * 123_606_528


def test_gmm_kernel_counts_against_hand_sums():
    c = counts.gmm_kernel_counts("best", rows=256, D=3, K=2)
    assert c["flops"] == 256 * 2 * (2 * 9 + 9)
    assert c["bytes"] == 4 * (256 * 3 + 2 * 256)
    c = counts.gmm_kernel_counts("loglik", rows=256, D=3, K=2)
    assert c["bytes"] == 4 * (256 * 3 + 256 * 2)  # the K-wide score kernel
    c = counts.gmm_kernel_counts("stats", rows=256, D=3, K=2)
    assert c["flops"] == 256 * 2 * (27 + 6 + 18 + 4)
    assert c["bytes"] == 4 * (256 * 3 + 2 * (2 * 13 + 1))
    r = counts.roofline_seconds(197e12, 819e9,
                                {"flops_bf16": 197e12,
                                 "hbm_bytes_per_s": 819e9})
    assert r["seconds"] == pytest.approx(1.0)
    assert counts.roofline_seconds(0.0, 819.0, {
        "flops_bf16": 1.0, "hbm_bytes_per_s": 819e9})["bound"] == "memory"


def test_answer_gap_agrees_on_the_references_own_nan():
    import numpy as np

    import jobs

    want = np.array([1.0, np.nan, -np.inf, 4.0])
    assert jobs.answer_gap(want.copy(), want) == 0.0
    assert jobs.answer_gap(want + [0.0, 0.0, 0.0, 1e-3], want) == \
        pytest.approx(1e-3 / 4.0)
    assert np.isnan(jobs.answer_gap([np.nan, np.nan, -np.inf, 4.0], want))
    assert jobs.answer_gap([1.0, np.nan, 0.0, 4.0], want) == np.inf


def test_seeded_burst_schedule_repeats_and_differs():
    train = harness.load_driver("train_loop")
    big = 2 ** 31 + 12345
    fb = json.loads((tiny.HERE / "traffic" / "train-stream.json")
                    .read_text())["faults"]
    d1 = train.burst_due_times(fb, 40.0, big)
    assert d1 == train.burst_due_times(fb, 40.0, big)
    assert d1 != train.burst_due_times(fb, 40.0, big + 1)
    gaps = [b - a for a, b in zip(d1, d1[1:])]
    assert all(fb["gap_s"][0] <= g <= fb["gap_s"][1] for g in gaps)
    assert d1[0] == fb["first_s"] and d1[-1] <= 40.0 - fb["tail_s"]


def test_seeded_feed_repeats_and_differs():
    import numpy as np

    train = harness.load_driver("train_loop")
    a = train.make_feed(2 ** 33 + 1, 3, 2, 16, 256, [0.9, 1.4])
    b = train.make_feed(2 ** 33 + 1, 3, 2, 16, 256, [0.9, 1.4])
    c = train.make_feed(2 ** 33 + 2, 3, 2, 16, 256, [0.9, 1.4])
    toks = [np.asarray(x["tokens"]) for x in a]
    assert all((t == np.asarray(y["tokens"])).all() for t, y in zip(toks, b))
    assert not (toks[0] == np.asarray(c[0]["tokens"])).all()
    rows = np.concatenate(toks)
    assert len({r.tobytes() for r in rows}) == rows.shape[0]  # rows differ
    assert (np.asarray(a[0]["labels"])[:, :-1] == toks[0][:, 1:]).all()


def test_a_cell_mix_and_metric_added_as_files(tmp_path):
    bench = tiny.tiny_tree(tmp_path)
    bench["workloads"].append({"name": "gpt2-train-new", "config": "gpt2-124m",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps.new", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step",
                               "moves": "tokens_per_s",
                               "workloads": ["gpt2-train-new"]})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("gpt2-train-new")
    mix = json.loads((tmp_path / "traffic" / "train-off.json").read_text())
    mix["batch"] = 2
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "gpt2-train-new.json").write_text(
        (tmp_path / "limits" / "gpt2-train-off.json").read_text())
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "steps.new.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    cell = harness.find_cell("gpt2-train-new", bench, here=tmp_path)
    assert cell.traffic["batch"] == 2 and cell.driver == "train_loop"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                     "setup_s"}
    assert [m["name"] for m in cell.per_layer][-1] == "steps.new"
    read = harness.metric_reader("steps.new", here=tmp_path)
    assert read(NS(steps=7)) == 7.0


def test_a_configuration_and_mix_added_as_files_need_no_edit(tmp_path):
    """A later configuration and mix arrive as new files and entries alone:
    the lookup finds them, and the tiny trees take a cell whose files carry
    ``tiny`` blocks and skip one whose files carry none."""
    import shutil

    src = tmp_path / "src"
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(tiny.HERE / sub, src / sub)
    bench = json.loads(json.dumps(harness.load_benchmark()))
    cfg = json.loads((src / "configs" / "gpt2-124m.json").read_text())
    cfg.pop("tiny")
    cfg["name"] = "dense-new"
    (src / "configs" / "dense-new.json").write_text(json.dumps(cfg))
    (src / "traffic" / "serve-new.json").write_text(
        json.dumps({"driver": "serve_new", "rate": 1.0}))
    mix = json.loads((src / "traffic" / "train-off.json").read_text())
    mix["tiny"]["seq"] = 16
    (src / "traffic" / "train-short.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "dense-new", "source": "x",
                             "file": "x", "reduced": [], "why": "x"})
    for name, config, traffic in (("dense-new-serve", "dense-new",
                                   "serve-new"),
                                  ("gpt2-train-short", "gpt2-124m",
                                   "train-short")):
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "x"})
        (src / "limits" / f"{name}.json").write_text(
            (src / "limits" / "gpt2-train-off.json").read_text())
    out = tmp_path / "out"
    tiny.tiny_tree(out, bench, src=src)
    assert (out / "limits" / "gpt2-train-short.json").exists()
    assert (out / "limits" / "gpt2-train-off.json").exists()
    assert not (out / "limits" / "dense-new-serve.json").exists()
    assert not (out / "configs" / "dense-new.json").exists()
    assert harness.find_cell("dense-new-serve", bench,
                             here=src).driver == "serve_new"
    short = harness.find_cell("gpt2-train-short", bench, here=out)
    assert short.traffic["seq"] == 16 and "tiny" not in short.traffic
    assert short.config["sizes"]["d_model"] == 64


def test_every_cell_has_its_files():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        for c in cell.limits["numbers"].values():
            assert c["lower"] < c["limit"]


def test_the_command_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(tiny.HERE / "run.py"), "--workload",
         "gpt2-train-off", "--seed", str(2 ** 32 + 5), "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
