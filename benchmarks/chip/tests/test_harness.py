"""Discovery (a fifth cell, a fourth configuration, a new driver and a new
per-layer metric are found with no edit to a file that is there), the
last-line contract, and BENCHMARK.json against the contract's own rules."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")

ECHO_DRIVER = '''
class Echo:
    def __init__(self, cell): self.facts = {"note": cell.traffic["note"]}
    def round(self, index): pass
    def verify(self, index): pass
    def finish(self): return 0
    def close(self): pass

def setup(cell, seed, devices, rehearsal):
    return Echo(cell)
'''


def test_a_cell_a_config_a_driver_and_a_layer_metric_are_found_as_files(tmp_path, capsys):
    home = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, home, ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in home.rglob("*") if p.is_file()}
    (home / "configs" / "echo8.json").write_text(json.dumps({"driver": "echo"}))
    (home / "traffic" / "echo-mix.json").write_text(
        json.dumps({"note": 42, "trace_rounds": 2}))
    (home / "drivers" / "echo.py").write_text(ECHO_DRIVER)
    (home / "layers" / "echo.note.py").write_text(
        "def read(window):\n    return window.facts['note'] + window.spans['bench.round'] * 0\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "echo8", "source": "x", "reduced": [], "why": "",
                            "file": "benchmarks/chip/configs/echo8.json"})
    spec["workloads"].append({"name": "echo-cell", "config": "echo8",
                              "traffic": "echo-mix", "chips": 1, "why": ""})
    spec["per_layer"].append({"name": "echo.note", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "echo",
                              "moves": "setup_s", "workloads": ["echo-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    code = harness.run(["--workload", "echo-cell", "--seed", "1", "--seconds", "0.05",
                        "--trace", "1", "--rehearsal"], 0.0, root=tmp_path)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] and line["attempted"] == 2
    assert "echo.note" in line["read"]
    assert all(p.read_bytes() == content for p, content in before.items())


def fake_window(**changes):
    window = harness.Window(facts={}, chips=1, device_kind="TPU v5 lite",
                            setup_s=1.5, attempted=4, memory_peak_bytes=123)
    for key, value in changes.items():
        setattr(window, key, value)
    return window


def test_the_result_line_has_the_contracts_keys_and_no_others():
    metrics = {"round_s": {"value": 0.05, "unit": "s"}}
    line = harness.result_line(fake_window(), 0, metrics, "tpu", 1, False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                              "memory_peak_bytes": 123}
    assert line["correct"] is True and line["failed"] == 0

    class Trace:
        busy_s, window_s = 0.9, 1.0
        def breakdown(self): return {"device_ops": [], "idle_gaps": []}

    traced = harness.result_line(fake_window(trace=Trace()), 0, {}, "tpu", 4, False)
    assert set(traced) == {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert traced["device"]["busy_s"] == 0.9 and traced["device"]["window_s"] == 1.0

    inexact = harness.result_line(fake_window(raised=1), 2, {}, "tpu", 1, False)
    assert inexact["correct"] is False and inexact["failed"] == 3


def test_a_rehearsal_says_so_and_prints_no_metric():
    metrics = {"round_s": {"value": 0.05, "unit": "s"}}
    line = harness.result_line(fake_window(), 0, metrics, "cpu", 1, True)
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["read"] == ["round_s"] and "memory_peak_bytes" not in line["device"]


def test_without_a_tpu_the_command_fails_and_prints_nothing():
    done = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert done.returncode != 0 and done.stdout == ""
    assert "needs 1 TPU chip" in done.stderr


def test_no_sda_variable_is_set_by_the_benchmark():
    for path in harness.HERE.rglob("*.py"):
        if "tests" not in path.parts:
            assert not re.search(r"environ\[[\"']SDA_|setdefault\([\"']SDA_|putenv",
                                 path.read_text()), path


# -- BENCHMARK.json against the contract's static rules ------------------------

def test_benchmark_json_keys_names_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(SPEC["command"]) <= 32 and 1 <= len(SPEC["paths"]) <= 16
    for path in SPEC["paths"]:
        assert PLAIN_PATH.match(path) and ".." not in path and not path.startswith("/")
        files = [p for p in (ROOT / path).rglob("*") if p.is_file()
                 and "__pycache__" not in p.parts and ".pytest_cache" not in p.parts]
        assert all(PLAIN_PATH.match(str(p.relative_to(ROOT))) for p in files)
    assert all(c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
               for c in SPEC["configs"])
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[key]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200 for e in SPEC["configs"] + SPEC["workloads"])
    cells = 2 + 14 * 24
    assert cells * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_config_is_used_and_every_cell_finds_its_files():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    for workload in SPEC["workloads"]:
        cell = harness.load_cell(ROOT, workload["name"])
        assert (cell.home / "drivers" / f"{cell.config['driver']}.py").is_file()
        assert (cell.home / "references" / f"{cell.config['reference']}.py").is_file()
        for kind, entries in (("end_to_end", cell.end_to_end), ("layers", cell.per_layer)):
            for entry in entries:
                assert (cell.home / kind / f"{entry['name']}.py").is_file(), entry
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer)


def test_metrics_state_source_and_bounds():
    for metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in SPEC["per_layer"]:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert "bound" not in metric and metric["layer"] and metric["moves"]
        assert LAYER.match(metric["layer"]), metric
    for config in SPEC["configs"]:
        body = json.loads((ROOT / config["file"]).read_text())
        assert body["reduced"] == config["reduced"] and body["source"] == config["source"]
        assert not any(re.search(r"(_dim|_rank)$|width|hidden", key)
                       for key in config["reduced"])


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_rehearses_end_to_end_on_the_cpu(workload):
    chips = next(w["chips"] for w in SPEC["workloads"] if w["name"] == workload)
    done = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", workload, "--seed", "3",
         "--seconds", "0.3", "--trace", "0", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "setup_s" in line["read"] and line["device"]["count"] == chips


def test_a_round_that_raises_is_counted_failed_and_not_verified():
    class Flaky:
        verified = []
        def round(self, index):
            if index == 1:
                raise RuntimeError("boom")
        def verify(self, index): self.verified.append(index)

    window, state = fake_window(attempted=0), Flaky()
    harness.run_window(state, 60.0, 4, window, harness.SpanLog(keep_intervals=True))
    assert (window.attempted, window.raised, state.verified) == (4, 1, [0, 2, 3])
    line = harness.result_line(window, 0, {}, "tpu", 1, False)
    assert line["correct"] is False and line["failed"] == 1

    class Broken:
        def round(self, index): raise RuntimeError("always")
        def verify(self, index): pass

    with pytest.raises(RuntimeError):
        harness.run_window(Broken(), 60.0, 10, fake_window(),
                           harness.SpanLog(keep_intervals=False))


#: what a later ``benchmark`` PR adds to BENCHMARK.json to make the federated
#: path a cell (PERF.md, Open questions): the files are already here
FEDERATED_ENTRIES = {
    "configs": [{"name": "fed-lenet8", "file": "benchmarks/chip/configs/fed-lenet8.json",
                 "source": "x", "reduced": ["participants"], "why": ""}],
    "workloads": [{"name": "fed-lenet", "config": "fed-lenet8",
                   "traffic": "fed-32x61706", "chips": 1, "why": ""}],
    "end_to_end": [{"name": "fed_round_s", "unit": "s", "better": "lower", "bound": 0.1,
                    "source": "host_clock", "workloads": ["fed-lenet"]}],
    "per_layer": [{"name": name, "unit": "s", "better": "lower", "source": "program_span",
                   "layer": "x", "moves": "fed_round_s", "workloads": ["fed-lenet"]}
                  for name in ("codec.encode_s_per_round", "role.participant_s_per_round",
                               "role.clerk_s_per_round", "server.snapshot_s_per_round",
                               "wire.upload_s_per_round")],
}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_federated_driver_rehearses_once_a_cell_names_it(tmp_path, capsys, trace):
    spec = json.loads(json.dumps(SPEC))
    for key, entries in FEDERATED_ENTRIES.items():
        spec[key] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "benchmarks" / "chip").symlink_to(harness.HERE)
    code = harness.run(["--workload", "fed-lenet", "--seed", "5", "--seconds", "1",
                        "--trace", str(trace), "--rehearsal"], 0.0, root=tmp_path)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    expected = ({"fed_round_s", "setup_s"} if trace == 0 else
                {e["name"] for e in FEDERATED_ENTRIES["per_layer"]})
    assert expected <= set(line["read"])
