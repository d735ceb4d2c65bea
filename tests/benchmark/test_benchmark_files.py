"""`BENCHMARK.json` and the files it names: everything exists and loads, names
and units keep to the allowed characters, the accepted cells are still there,
the operation counts agree with a hand count, and a cell, a configuration, a
traffic mix and a per-layer metric can each be added as new files plus one
entry. What is asserted of `BENCHMARK.json` is a rule and never a count of
its entries: each check is a function of `(spec, root)`, run on the repo and,
by `test_a_further_configuration_goes_in_as_new_files_and_entries`, on a copy
that a further configuration was added to."""

import importlib.util
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

REPO = tiny.REPO
sys.path.insert(0, REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# What the accepted benchmark holds: a later PR adds to it and takes nothing
# away (only a `benchmark` PR may, and it changes this with it).
ACCEPTED = {"gpt2_small.train_b16_s1024": "gpt2_small",
            "trinity_mini.train_b1_s8192": "trinity_mini",
            "kimi_linear.train_b1_s8192_ep32": "kimi_linear"}
MAX_CELLS = 24


def spec(root=REPO):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load(path):
    s = importlib.util.spec_from_file_location("m_" + str(abs(hash(path))),
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def check_keys_names_and_units(sp):
    assert set(sp) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(sp["run_seconds"], int) and 1 <= sp["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in sp[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in sp["end_to_end"] + sp["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in sp["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in sp["end_to_end"])
    e2e = {m["name"] for m in sp["end_to_end"]}
    for m in sp["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    for w in sp["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in sp["workloads"])
    assert four <= max(1, len(sp["workloads"]) // 4)
    assert len(sp["workloads"]) <= MAX_CELLS


def check_the_accepted_cells_are_still_there(sp):
    cells = {w["name"]: w for w in sp["workloads"]}
    assert set(ACCEPTED) <= set(cells)
    assert set(ACCEPTED.values()) <= {c["name"] for c in sp["configs"]}
    rate = {m["name"]: m for m in sp["end_to_end"]}["train_examples_per_s"]
    for name, config in ACCEPTED.items():
        assert cells[name]["config"] == config and cells[name]["chips"] == 1
        assert name in rate["workloads"]


def check_every_named_file_exists_and_loads(sp, root):
    configs = {c["name"]: c for c in sp["configs"]}
    used = set()
    for c in sp["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in sp["paths"]))
        with open(os.path.join(root, c["file"])) as fh:
            cfg = json.load(fh)
        ref = os.path.join(root, "benchmark", "references",
                           cfg["reference"] + ".py")
        assert hasattr(load(ref), "init_params")
        counts = os.path.join(root, "benchmark", "counts",
                              cfg["counts"] + ".py")
        assert hasattr(load(counts), "train_ops_per_example")
    cells = set()
    for w in sp["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        with open(os.path.join(root, "benchmark", "traffic",
                               w["traffic"] + ".json")) as fh:
            traffic = json.load(fh)
        assert os.path.isfile(os.path.join(
            root, "benchmark", "runners", traffic["runner"] + ".py"))
        assert traffic["limits"] and traffic["who"]
    assert used == set(configs), "a configuration no cell uses"
    names = {w["name"] for w in sp["workloads"]}
    for m in sp["per_layer"]:
        reader = os.path.join(root, "benchmark", "metrics",
                              m["name"] + ".py")
        assert hasattr(load(reader), "read"), m["name"]
    for m in sp["end_to_end"] + sp["per_layer"]:
        assert set(m.get("workloads", [])) <= names
    with open(os.path.join(root, "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)
    assert peaks["source"] and "TPU v5 lite" in peaks["by_device_kind"]


def test_keys_names_and_units():
    check_keys_names_and_units(spec())


def test_the_accepted_cells_are_still_there():
    check_the_accepted_cells_are_still_there(spec())


def test_every_named_file_exists_and_loads():
    check_every_named_file_exists_and_loads(spec(), REPO)


def test_unknown_device_is_an_error():
    from benchmark import harness
    with pytest.raises(harness.CellError):
        harness.peaks_for("TPU v9 imaginary")


def test_causal_operation_count_against_a_hand_count():
    counts = load(os.path.join(REPO, "benchmark", "counts",
                               "transformer.py"))
    # by hand: one layer, hidden 4, feed-forward 16, 3 positions, vocab 10
    cfg = {"n_layer": 1, "n_embd": 4, "n_inner": None, "vocab_size": 10}
    linear = 4 * 2 * 3 * 4 * 4          # q, k, v, o: 384
    mlp = 2 * 2 * 3 * 4 * 16            # 768
    attention = 2 * 2 * (3 * 4 / 2) * 4  # 6 causal pairs, QK^T and PV: 96
    head = 2 * 2 * 4 * 10               # the 2 positions that have a target
    assert counts.forward_ops_per_example(cfg, 3) == \
        linear + mlp + attention + head
    with open(os.path.join(REPO, "benchmark", "configs",
                           "gpt2_small.json")) as fh:
        gpt2 = json.load(fh)
    ops = counts.train_ops_per_example(gpt2, {"seq_len": 1024})
    assert abs(ops - 816.8e9) / 816.8e9 < 2e-3
    # the full-square count the program's flops.py makes is ~7 % higher
    square = ops + 3 * 12 * 2 * 2 * 768 * (1024 * 1023 / 2)
    assert 1.05 < square / ops < 1.08
    flash = load(os.path.join(REPO, "benchmark", "counts",
                              "flash_attention.py"))
    assert flash.train_ops(gpt2, 1024) == \
        3 * counts.attention_forward_ops(gpt2, 1024)


def test_generator_gives_every_seed_the_same_work():
    from benchmark import generators
    traffic = dict(tiny.SERVE_TINY, rate_rps=50.0)
    a = generators.make_requests(traffic, 1024, 1, 4.0)
    b = generators.make_requests(traffic, 1024, 2 ** 31 + 7, 4.0)
    assert a[0]["prompt"] != b[0]["prompt"]
    for key in ("max_new_tokens",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert abs(len(a) - 200) <= 2 and all(r["arrival_s"] < 4.0 for r in a)
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 4 and max(lens) <= 30
    assert a == generators.make_requests(traffic, 1024, 1, 4.0)


def test_things_are_added_as_new_files_plus_one_entry(tmp_path):
    co = tiny.make_checkout(str(tmp_path))  # asserts no file is overwritten
    with open(os.path.join(co, "BENCHMARK.json")) as fh:
        sp = json.load(fh)
    assert {"gpt_tiny.train_b4_s64", "resnet_tiny.train_b8_i32",
            "gpt_tiny.serve_tiny"} <= {w["name"] for w in sp["workloads"]}
    for rel in ("benchmark/run.py", "benchmark/harness.py",
                "benchmark/runners/train.py", "benchmark/trace.py"):
        with open(os.path.join(co, rel)) as a, \
                open(os.path.join(REPO, rel)) as b:
            assert a.read() == b.read()



FURTHER_CELL = "further_tiny.train_b2_s32"


def test_a_further_configuration_goes_in_as_new_files_and_entries(tmp_path):
    """What a `model_config` PR does to the repo, done to a copy: three new
    files, two new entries, the cell's name on the list of the end-to-end
    metric it reports, one per-layer entry of its own. Every rule the
    benchmark's tests hold the repo's `BENCHMARK.json` to then holds for the
    copy's, and the new cell runs, found by its names alone."""
    import test_afmoe_cell
    import test_anatomy_readers
    import test_kimi_cell

    co = tiny.make_checkout(str(tmp_path))
    tiny.add(co, "benchmark/configs/further_tiny.json",
             json.dumps(tiny.GPT_TINY))
    tiny.add(co, "benchmark/traffic/train_b2_s32.json",
             json.dumps(dict(tiny.TRAIN_TINY, batch=2, seq_len=32)))
    tiny.add(co, "benchmark/metrics/dispatch_count.further.py",
             tiny.TINY_METRIC)
    sp = spec(co)
    sp["configs"].append({
        "name": "further_tiny", "source": tiny.GPT_TINY["source"],
        "file": "benchmark/configs/further_tiny.json", "reduced": [],
        "why": "tests only"})
    sp["workloads"].append({
        "name": FURTHER_CELL, "config": "further_tiny",
        "traffic": "train_b2_s32", "chips": 1, "why": "tests only"})
    rate = {m["name"]: m for m in sp["end_to_end"]}["train_examples_per_s"]
    rate["workloads"].append(FURTHER_CELL)
    sp["per_layer"].append({
        "name": "dispatch_count.further", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "loop",
        "moves": "train_examples_per_s", "workloads": [FURTHER_CELL]})
    with open(os.path.join(co, "BENCHMARK.json"), "w") as fh:
        json.dump(sp, fh)

    sp = spec(co)
    check_keys_names_and_units(sp)
    check_the_accepted_cells_are_still_there(sp)
    check_every_named_file_exists_and_loads(sp, co)
    test_afmoe_cell.check_the_cells_metrics(sp)
    test_kimi_cell.check_the_cells_metrics(sp)
    test_anatomy_readers.check_the_device_ms_entries(sp)

    rc, out, err = tiny.run_cell(
        co, "--workload", FURTHER_CELL, "--seed", str(2 ** 31 + 33),
        "--seconds", "5", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"dispatch_count.further", "dispatch_ms.train"} <= \
        set(line["metrics"])
    assert "dispatch_count.train" not in line["metrics"]
