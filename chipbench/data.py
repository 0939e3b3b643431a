"""Where the benchmark's data files are and how each is found by name.

Everything that belongs to one cell, one configuration, one traffic mix or
one per-layer metric is a file of its own; BENCHMARK.json gives the names and
this module turns a name into a file or a module. Adding a cell never edits a
file that is there (README.md).
"""

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# what a run leaves behind, all of it git-ignored: the serving export cache
# and the profiler's traces. A fixed path inside the checkout.
SCRATCH = os.path.join(ROOT, ".chipbench_cache")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check_name(name):
    if not _NAME.match(str(name)):
        raise ValueError("not a benchmark name: %r" % (name,))
    return str(name)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def named(kind, name):
    """chipbench/<kind>/<name>.json, for kind in workloads, configs, traffic,
    layer_metrics."""
    return load_json(kind, _check_name(name) + ".json")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name):
    """A cell as the runner gets it: its workload file, with the
    configuration and the traffic mix it names loaded beside it."""
    workload = named("workloads", name)
    workload["name"] = name
    return {"workload": workload,
            "config": named("configs", workload["config"]),
            "traffic": named("traffic", workload["traffic"])}


def module(kind, name):
    """chipbench/<kind>/<name>.py, for kind in runners, readers, traffic,
    programs."""
    return importlib.import_module(
        "chipbench.%s.%s" % (_check_name(kind), _check_name(name))
    )


def cell_metrics(man, cell_name, group):
    """The metrics of `group` ('end_to_end' or 'per_layer') that this cell
    reports: those with no 'workloads' key and those that list the cell."""
    return [
        m for m in man[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def peaks(device_kind):
    table = load_json("peaks.json")["peaks"]
    if device_kind not in table:
        raise RuntimeError(
            "no published peaks for device kind %r (chipbench/peaks.json "
            "holds %s)" % (device_kind, sorted(table))
        )
    return table[device_kind]
