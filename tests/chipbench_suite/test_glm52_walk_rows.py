"""serve.sparse_walk_rows_mean, the rows the glm_5_2 decode step's sparse
latent walk gathers for: read in the toy cell through run.main() (traced),
listed for glm52_longdocs alone, and absent on a program without the
histogram."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data  # noqa: E402
from test_chipbench import on_cpu, run_main, tree  # noqa: E402,F401 — fixtures
from test_glm52_cell import _toy_cell, synthetic_dsa_trace  # noqa: E402,F401


def test_the_walk_s_rows_are_read_in_the_traced_toy_cell(tree, on_cpu, synthetic_dsa_trace):
    """The toy engine's 8 slots are one block: a decode step with a live row
    walks 8."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_glm_docs", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_glm_docs", "per_layer")}
    assert "serve.sparse_walk_rows_mean" in names
    assert doc["metrics"]["serve.sparse_walk_rows_mean"]["value"] == 8


def test_the_walk_s_rows_are_read_in_this_cell_alone():
    """serve.sparse_walk_rows_mean is listed for glm52_longdocs and no other
    cell, and reads nothing from a program without the histogram (a parent
    whose walk gathers for every slot)."""
    m = data.manifest()
    cells = [w["name"] for w in m["workloads"]
             if "serve.sparse_walk_rows_mean" in {
                 x["name"] for x in data.cell_metrics(m, w["name"], "per_layer")}]
    assert cells == ["glm52_longdocs"]
    spec = data.named("layer_metrics", "serve.sparse_walk_rows_mean")
    assert spec["reader"] == "histogram_window_mean"
    reader = data.module("readers", spec["reader"])
    run = {"names": {"model": "glm52"}, "histograms": {
        "serving/glm52/gen_dsa_selected_share": ((0.0, 0), (40.0, 10))}}
    assert reader.read(spec["args"], run) is None
    run["histograms"]["serving/glm52/gen_dsa_walk_rows"] = ((16.0, 2), (96.0, 12))
    assert reader.read(spec["args"], run) == 8.0
