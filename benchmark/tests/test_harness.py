"""The harness end to end on the CPU at tiny sizes, and the contract's rules
on BENCHMARK.json and its data files."""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny")
    tiny.make_root(path)
    return path


def test_names_units_and_files(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    from harness import families

    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(tiny.REPO, c["file"]))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            family = families.of(json.load(f))  # no key, no module: raises
        missing = [n for n in tiny.FAMILY_NAMES if not hasattr(family, n)]
        assert not missing, (c["name"], family.__name__, missing)
    for w in bench["workloads"]:
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(
                tiny.REPO, "benchmark", sub, f"{name}.json")), (sub, name)
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            tiny.REPO, "benchmark/layer_metrics", f"{m['name']}.json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in bench["end_to_end"])


def test_every_layer_metric_moves_a_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:  # every cell: setup_s, one more, one per-layer
        mine = [m for m in e2e.values()
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


@pytest.mark.parametrize("workload", ["train-seq4096", "serve-chat-open",
                                      "serve-decode-closed"])
def test_a_cell_runs_and_prints_the_contract_line(root, bench, workload):
    if workload not in {w["name"] for w in bench["workloads"]}:
        pytest.skip("cell not in BENCHMARK.json")
    res, last = tiny.run_cell(root, workload)
    assert set(res) == RESULT_KEYS | {"checks"} and last.startswith("{")
    assert list(res)[-1] == "checks" and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_same_seed_same_traffic_another_seed_another():
    from harness import traffic

    def load_mix(name):
        with open(os.path.join(tiny.REPO, "benchmark/traffic",
                               f"{name}.json")) as f:
            return json.load(f)

    mix = load_mix("chat-open")
    a = traffic.open_requests(mix, 2**31 + 5, 10, 1000)
    b = traffic.open_requests(mix, 2**31 + 5, 10, 1000)
    c = traffic.open_requests(mix, 7, 10, 1000)
    assert a == b and a != c
    # every seed sends the same schedule, with other tokens
    win = lambda r: [(t, len(p)) for t, p in r if 0 <= t < 10]
    assert win(a) == win(c) and len(win(a)) == round(10 * 0.7)
    assert len({len(p) for _, p in a}) > 10  # lengths do vary
    assert len(a) == len(c) and all(t < 10 for t, _ in a)
    assert min(t for t, _ in a) == -mix["ramp_s"]
    tr = dict(load_mix("pretrain-4096"), seq=16, n_batches=3)
    x, _ = traffic.train_batches(tr, 2**31 + 5, 2, 100)
    y, _ = traffic.train_batches(tr, 2**31 + 5, 2, 100)
    z, labels = traffic.train_batches(tr, 8, 2, 100)
    assert (x == y).all() and not (x == z).all()
    assert (z[..., 1:] == labels[..., :-1]).all()
    rows = {tuple(r) for r in z.reshape(-1, 16).tolist()}
    assert len(rows) == 3 * 2  # all rows differ


def test_new_config_mix_and_metric_are_new_files_only(tmp_path, bench):
    """A later PR adds a configuration, a mix and a per-layer metric over an
    existing reader as files and entries, editing no file that is there."""
    b = tiny.make_root(tmp_path)
    bdir = tmp_path / "benchmark"
    cfg = json.loads((bdir / "configs/internlm2-1.8b-train.json").read_text())
    cfg.update(num_attention_heads=2, num_key_value_heads=2)
    tiny._dump(cfg, str(bdir / "configs/throwaway.json"))
    mix = json.loads((bdir / "traffic/pretrain-4096.json").read_text())
    tiny._dump(dict(mix, seq=16), str(bdir / "traffic/throwaway-16.json"))
    tiny._dump(tiny.TRAIN_LIMITS, str(bdir / "limits/throwaway.json"))
    tiny._dump({"reader": "trace.counter",
                "args": {"key": "planner_batch_tokens", "scale": 0.5}},
               str(bdir / "layer_metrics/throwaway.half_batch.json"))
    b["configs"].append({"name": "throwaway", "source": "none",
                         "file": "benchmark/configs/throwaway.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "throwaway", "config": "throwaway",
                           "traffic": "throwaway-16", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({
        "name": "throwaway.half_batch", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "planner",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["throwaway"]})
    for m in b["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("throwaway")
    tiny._dump(b, str(tmp_path / "BENCHMARK.json"))
    res, _ = tiny.run_cell(tmp_path, "throwaway")
    assert res["correct"] and "train_tokens_per_s_per_chip" in res["metrics"]

    import run
    from harness import trace

    _, cell = run.load_cell(str(tmp_path), "throwaway")
    ctx = {"counters": {"planner_batch_tokens": 32.0, "compile_s": 1.0},
           "spans": trace.Spans(), "t0": 0, "t1": 1}
    got = run.read_layer_metrics(b, cell, ctx)
    assert got["throwaway.half_batch"] == {"value": 16.0, "unit": "tokens"}
    assert "planner.batch_tokens" not in got  # not this cell's


def test_no_chip_no_result(capsys):
    import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "train-seq4096", "--seed", "1", "--seconds",
                  "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
