"""A configuration, a traffic mix and a per-layer metric are new files,
found by the names in the spec: adding one edits no file."""
import io
import json
import types
from contextlib import redirect_stdout

import jax

from chipbench import harness, run
from conftest import DATA, ROOT

READER = '''"""Throwaway reader: the number of traced cohort spans."""


def read(run):
    n = sum(s["name"] == "frontend.cohort" for s in run.spans)
    return float(n) if n else None
'''


def test_new_files_are_found_by_name(tmp_path, tiny_spec):
    name = "zz_throwaway.cohorts"
    reader = harness.BENCH / "metrics" / f"{name}.py"
    traffic = tmp_path / "traffic"
    traffic.mkdir()
    (traffic / "zz-mix.json").write_text(json.dumps(
        {"arrivals": "open", "rate_qps": 40}))
    cfg = json.loads((DATA / "tiny-linf.json").read_text())
    cfg_file = tmp_path / "zz-config.json"
    cfg_file.write_text(json.dumps(cfg))
    spec = json.loads(json.dumps(tiny_spec))
    spec["configs"].append({"name": "zz-config", "source": "test",
                            "file": str(cfg_file), "reduced": [],
                            "why": "test"})
    wl = {"name": "zz.cell", "config": "zz-config", "traffic": "zz-mix",
          "chips": 1, "why": "test"}
    spec["workloads"].append(wl)
    spec["per_layer"].append({"name": name, "unit": "count",
                              "better": "higher", "source": "program_span",
                              "layer": "front end", "moves": "query_p99_ms",
                              "workloads": ["zz.cell"]})
    reader.write_text(READER)
    try:
        args = types.SimpleNamespace(workload="zz.cell", seed=9,
                                     seconds=1.0, trace=1)
        buf = io.StringIO()
        with redirect_stdout(buf):
            assert run.run_cell(spec, wl, args, jax.devices()[:1],
                                traffic) == 0
    finally:
        reader.unlink()
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["correct"]
    assert res["metrics"][name]["value"] >= 1
    assert "frontend.queue_ms" in res["metrics"]
    assert (ROOT / "BENCHMARK.json").is_file()
