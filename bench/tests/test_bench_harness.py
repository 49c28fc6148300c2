"""The harness end to end on the CPU at a tiny size, with the look for a
chip skipped: discovery by file name, refusal off a TPU or outside a
checkout, a clean run that is correct, a run whose answers are altered
where the program produces them, and the controls."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import run

TINY = {
    "sift": dict(n_objects=4096, n_segments=2, queries_per_batch=4,
                 query_pool=256, check_requests=32),
    "adult": dict(n_objects=4000, queries_per_batch=16, query_pool=512,
                  check_requests=8),
}
CELLS = {"sift": ("sift-closed", dict(clients=4)),
         "adult": ("adult-closed", dict(clients=4, rows=4))}


def tiny(config: str):
    cell, cfg, traffic, layer = run.resolve(CELLS[config][0])
    cfg = dict(cfg, **TINY[config])
    return cell, cfg, dict(traffic, **CELLS[config][1]), layer


def run_tiny(config: str, seed: int, tmp_path):
    cell, cfg, traffic, layer = tiny(config)
    return run.run_cell(cell, cfg, traffic, layer, seed, 1.0, False,
                        time.perf_counter(), out_dir=str(tmp_path))


def test_new_pieces_are_found_by_file_name(tmp_path, monkeypatch):
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps({"name": "toy"}))
    (tmp_path / "traffic" / "burst-2.json").write_text(json.dumps(
        {"loop": "open", "rate": 2.0, "rows": 1, "k": 5}))
    (tmp_path / "metrics" / "toy_ms.py").write_text(
        "def read(ctx):\n    return ctx.rows * 2.0\n")
    monkeypatch.setattr(run, "BENCH", str(tmp_path))
    doc = {"workloads": [{"name": "toy.burst", "config": "toy",
                          "traffic": "burst-2", "chips": 1}],
           "end_to_end": [{"name": "qps"}, {"name": "p95", "workloads": ["x"]}],
           "per_layer": [{"name": "toy_ms", "unit": "ms"},
                         {"name": "other", "unit": "ms", "workloads": ["x"]}]}
    cell, cfg, traffic, layer = run.resolve("toy.burst", doc)
    assert cfg == {"name": "toy"} and traffic["rate"] == 2.0
    assert cell["end_to_end"] == ["qps"]
    assert [m["name"] for m in layer] == ["toy_ms"]
    assert run.per_layer(layer, None, dict(rows=3)) == {
        "toy_ms": {"value": 6.0, "unit": "ms"}}
    with pytest.raises(run.Refused):
        run.resolve("nope", doc)


def test_every_named_piece_exists():
    doc = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for w in doc["workloads"]:
        cell, cfg, traffic, layer = run.resolve(w["name"], doc)
        for suffix in ("", "_reference"):
            run.find("configs", cfg["name"] + suffix, ".py")
        for m in layer:
            assert callable(run.module("metrics", m["name"]).read)


def _main(env_extra, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, os.path.join(run.BENCH, "run.py"),
                           "--workload", "sift-closed", "--seed", "1",
                           "--seconds", "1", *args],
                          capture_output=True, text=True, env=env, timeout=300)


def test_refuses_a_platform_that_is_not_a_tpu():
    p = _main({})
    assert p.returncode == 1
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_outside_a_checkout(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(run, "SRC", str(tmp_path))
    assert run.main(["--workload", "sift-closed", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert "no program" in out.err and out.out == ""


def test_references_import_nothing_of_the_program():
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "import sift_reference, adult_reference, check, devtrace, load, peaks; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))"
            % (run.BENCH, os.path.join(run.BENCH, "configs")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


@pytest.mark.parametrize("config", ["sift", "adult"])
def test_tiny_run_is_correct(config, tmp_path):
    res = run_tiny(config, 2**31 + 17, tmp_path)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == ({"qps", "latency_p50_ms", "setup_s"}
                                   | ({"latency_p95_ms"} if config == "sift" else set()))
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_tiny_open_loop_run_is_correct(tmp_path):
    """An open-loop mix: requests fall due at the seeded Poisson times, all
    of them are sent and answered, and latency runs from the due time."""
    cell, cfg, traffic, layer = tiny("sift")
    traffic = {"loop": "open", "rate": 12.0, "rows": 1, "k": 100}
    res = run.run_cell(cell, cfg, traffic, layer, 2**33 + 9, 1.0, False,
                       time.perf_counter(), out_dir=str(tmp_path))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("config", ["sift", "adult"])
def test_altered_answer_is_not_correct(config, tmp_path, monkeypatch):
    """Every answer gets its best id moved by one, where the program
    produces it (the merge of the segments' candidates)."""
    from repro.core import merge
    from repro.core.types import TopKResult

    real = merge.merge_ragged

    def altered(ids_list, counts_list, k):
        res = real(ids_list, counts_list, k)
        return TopKResult(ids=res.ids.at[:, 0].add(1), counts=res.counts,
                          threshold=res.threshold)

    monkeypatch.setattr(merge, "merge_ragged", altered)
    res = run_tiny(config, 5, tmp_path)
    assert res["correct"] is False
    assert res["checks"]["rank_mismatch"]["value"] > 0


@pytest.mark.parametrize("config,seeds", [("sift", (3, 4, 5)), ("adult", (3, 4, 5))])
def test_control_fails_the_limits(config, seeds):
    """The control (SIFT: projections at bf16 three-pass precision; Adult:
    ties broken towards high ids) put in the program's place, compared as a
    run compares the program."""
    import check

    _, cfg, traffic, _ = tiny(config)
    build = run.module("configs", config)
    ref = run.module("configs", config + "_reference")
    failed = []
    for seed in seeds:
        q = build.queries(cfg, seed, 64)
        ids, counts, _ = ref.reference(cfg, seed, q, np.zeros((64, 100), np.int32), 100,
                                       control=True)
        want_ids, want_counts, recount = ref.reference(cfg, seed, q, ids, 100)
        nums = check.compare(ids, counts, want_ids, want_counts, recount)
        failed.append(not check.judge(nums, cfg["limits"]))
    assert all(failed)
