"""BENCHMARK.json against the rules it is held to, and the harness's
discovery of configurations, mixes and metrics by name."""

import os
import re
import subprocess
import sys

import pytest

import benchkit

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return benchkit.benchmark()


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys(bench):
    d = bench.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(d["paths"]) <= 16
    for p in d["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(benchkit.ROOT, p))
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(benchkit.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_configs_and_cells(bench):
    d = bench.data
    cfg_names = [c["name"] for c in d["configs"]]
    assert len(set(cfg_names)) == len(cfg_names)
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["why"])
        assert c["source"].startswith("https://")
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
        full = bench.config(c["name"])
        assert full["source"] == c["source"]
        for k in c["reduced"]:
            assert full["published"][k] != full[k]
        bench.family(full["family"])
    used = set()
    cells = [w["name"] for w in d["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = set()
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        mix = bench.traffic(w["traffic"])
        assert mix["loop"] in ("closed", "open")
    assert used == set(cfg_names)
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(
        1, len(cells) // 2)


def test_metrics(bench):
    d = bench.data
    cells = {w["name"] for w in d["workloads"]}
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in d["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        bench.reader(m["name"])             # its reader file exists
    for m in d["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _one_line(m["layer"])
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m[
                "name"]:
            assert m["unit"] == "%"
    for cell in cells:
        got = [m["name"] for m in bench.metrics(cell, "end_to_end")]
        assert "setup_s" in got and len(got) >= 2
        assert bench.metrics(cell, "per_layer")


def test_harness_finds_a_new_metric_by_name(tmp_path, bench):
    """A metric added as a file is found by its name; nothing else
    changes."""
    (tmp_path / "tokens_seen.extra.py").write_text(
        "def read(rec):\n    return float(len(rec['requests']))\n")
    read = bench.reader("tokens_seen.extra", str(tmp_path))
    assert read({"requests": [1, 2, 3]}) == 3.0
    with pytest.raises(FileNotFoundError):
        bench.reader("no_such_metric", str(tmp_path))


def test_cell_resolution(bench):
    cell = bench.cell("granite2b-decode-backlog")
    cfg = bench.config(cell["config"])
    assert cfg["serving"]["max_slots"] == 16
    assert bench.traffic(cell["traffic"])["clients"] == 32
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")


def test_unknown_device_kind_is_refused():
    import device
    with pytest.raises(SystemExit):
        device.peaks("TPU v99")
    with pytest.raises(SystemExit):
        device.peaks("cpu")


def test_run_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "granite2b-decode-backlog", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=benchkit.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
