"""chipbench.sweep for a serving cell whose configuration names another
runner than `serve`: the same windows, the same table and the same rule for
"sustained" (sweep.one_rate), with the model built by the cell's own runner.
sweep.py builds through runners/serve.py by name and is not edited.

    python -m chipbench.sweep_runner --workload lfm2moe_chat --rates 3,4,5,6 --seconds 30
"""

import argparse
import sys

from chipbench import data, run as cb_run, sweep


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = data.cell(args.workload)
    cb_run.devices_or_exit(cell["workload"]["chips"])
    cb_run.place_caches()
    runner = data.module("runners", cell["config"]["runner"])
    server, engine, _, name = runner.build(cell, args.seed)
    timeout_s = cell["config"]["server"]["request_timeout_ms"] / 1e3
    port = server.start()
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        rows.append(sweep.one_rate(port, engine, name, cell, args.seed, rate,
                                   args.seconds, timeout_s))
        print("sweep: " + " ".join(
            "%s=%s" % (k, sweep._fmt(v)) for k, v in rows[-1].items()),
            flush=True)
    server.stop(drain=False)
    good = [r["rate"] for r in rows if r["sustained"]]
    print("sweep: highest sustained rate %s requests/s; a cell below it "
          "takes 0.8 x that" % (max(good) if good else "none of these"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
