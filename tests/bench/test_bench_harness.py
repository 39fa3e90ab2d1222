"""The harness is driven by data: a cell defined only by new files and a
new BENCHMARK.json entry (a throwaway configuration, mix, limits file and
per-layer metric under a temporary root) runs end to end; the traffic is a
pure function of the seed; without a TPU the run prints no result."""
import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402


def test_new_closed_loop_cell_runs_from_new_files(tmp_path):
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_closed", seed=3)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap_max"]["limit"] == 1e-3


def test_new_traced_cell_reads_its_own_metric(tmp_path):
    out, _ = tiny_cell.run_tiny(tmp_path, "tiny_closed", seed=2**31 + 7,
                                trace=1)
    assert out["correct"] is True
    assert out["metrics"]["tiny_metric"]["value"] == 42.0
    idle = out["metrics"]["idle_share.decode"]["value"]
    assert 0 < idle < 100
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(out["breakdown"][key]) <= 10


def test_new_train_cell_runs_from_new_files(tmp_path):
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_train", seed=11)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert set(checks) == {"loss_gap", "gnorm_gap", "grad_norm_gap",
                           "update_norm_gap"}


@pytest.mark.parametrize("mix", ["tiny_closed", "tiny_train"])
def test_second_architecture_cell_runs_from_new_files(tmp_path, mix):
    """granite-20b's layout (untied head, no qk-norm, one kv head): its
    configuration, reference and mix are new files under the root."""
    out, _ = tiny_cell.run_tiny(tmp_path, mix, arch="granite",
                                seed=2**31 + 21)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    kind = tiny_cell.MIXES[mix]["kind"]
    assert set(out["checks"]) == set(tiny_cell.LIMITS[kind])
    assert os.path.exists(tmp_path / "bench" / "refs" / "granite.py")


@pytest.mark.parametrize("mix", ["tiny_closed", "tiny_train"])
def test_second_architecture_control_fails(tmp_path, mix):
    """Its reference in float8, put in the program's place, is judged not
    correct: the fixture reference separates the two readings."""
    out, _ = tiny_cell.run_tiny(tmp_path, mix, arch="granite", seed=5,
                                control="fp8")
    assert out["correct"] is False


def test_traffic_is_a_pure_function_of_the_seed():
    from bench import traffic
    mix = tiny_cell.MIXES["tiny_closed"]
    a = traffic.serve_requests(mix, 2**31 + 5, 512)
    b = traffic.serve_requests(mix, 2**31 + 5, 512)
    c = traffic.serve_requests(mix, 6, 512)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new == y.max_new
    # another seed: the same sizes, in another order
    assert tiny_cell.lengths(a) == tiny_cell.lengths(c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    t1 = traffic.train_batches(tiny_cell.MIXES["tiny_train"], 9, 512)
    t2 = traffic.train_batches(tiny_cell.MIXES["tiny_train"], 9, 512)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(t1, t2))
    assert all(np.array_equal(x[0][:, 1:], x[1][:, :-1]) for x in t1)


def test_no_tpu_no_result(capsys):
    from bench import run
    rc = run.main(["--workload", "qwen3-batch-decode", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "needs a TPU" in out.err


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_its_files():
    root = tiny_cell.ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(root, c["file"]))
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.exists(os.path.join(root, "bench", "refs",
                                           cfg["reference"] + ".py"))
    names = [w["name"] for w in spec["workloads"]]
    assert len(set(names)) == len(names)
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        for part in (("traffic", w["traffic"]), ("cells", w["name"])):
            assert os.path.exists(os.path.join(root, "bench", part[0],
                                               part[1] + ".json"))
        e2e = [m["name"] for m in spec["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m["workloads"] for m in spec["per_layer"])
    for m in spec["per_layer"]:
        assert NAME.match(m["name"])
        assert os.path.exists(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
