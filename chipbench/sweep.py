"""Find the highest rate a serving cell sustains: one process, the model
loaded once, one window at each of a few offered rates, a table, no result
line. Run once when a cell is defined; the cell's traffic file then fixes
the rate at 0.8 x the highest sustained one (PERF.md keeps the table).

    python -m chipbench.sweep --workload gpt2l_chat --rates 1.5,2,2.5,3 --seconds 30

"Sustained": the count of requests sent and not yet answered does not grow
over the last third of the window (by more than 2 or a tenth), and fewer than
1 % of the requests are refused or fail.
"""

import argparse
import sys
import time

import numpy as np

from chipbench import data, run as cb_run
from chipbench.runners import serve


def outstanding(gen, at_s):
    sent = sum(1 for r in gen.schedule if r["due_s"] <= at_s)
    done = sum(1 for r in gen.records if r is not None and r["done_s"] <= at_s)
    return sent - done


def one_rate(port, engine, name, cell, seed, rate, seconds, timeout_s):
    cell = dict(cell, traffic=dict(cell["traffic"], rate_per_s=rate))
    gen = serve.generator(port, name, cell, seed, [("window", seconds)],
                          timeout_s + 5.0)
    hist0, _ = serve.snapshot(engine)
    gen.start()
    time.sleep(seconds)
    hist1, _ = serve.snapshot(engine)
    every = list(range(len(gen.schedule)))
    gen.join(every, timeout_s + 10.0)
    gen.stop_dispatch()
    recs = [r for r in gen.records if r is not None]
    ok = [(r, s) for r, s in zip(gen.records, gen.schedule)
          if r is not None and r["status"] == 200]
    lat = [(r["done_s"] - s["due_s"]) * 1e3 / r["tokens"] for r, s in ok]
    tokens = sum(r["tokens"] for r, _ in ok if r["done_s"] < seconds)
    o = [outstanding(gen, seconds * f) for f in (1 / 3.0, 2 / 3.0, 1.0)]
    bad = len(gen.schedule) - len(ok)
    step = "serving/%s/gen_step_ms" % name
    (s0, c0), (s1, c1) = hist0.get(step, (0.0, 0)), hist1[step]
    row = {
        "rate": rate, "sent": len(gen.schedule), "refused_or_failed": bad,
        "tokens_per_s": tokens / seconds,
        "ms_per_token_p50": float(np.percentile(lat, 50)) if lat else None,
        "ms_per_token_p90": float(np.percentile(lat, 90)) if lat else None,
        "outstanding_1/3": o[0], "outstanding_2/3": o[1], "outstanding_end": o[2],
        "decode_step_mean_ms": (s1 - s0) / max(c1 - c0, 1),
        "drain_s": max(r["done_s"] for r in recs) - seconds if recs else None,
    }
    row["sustained"] = (
        o[2] <= o[1] + max(2.0, 0.1 * o[1]) and bad < 0.01 * len(gen.schedule))
    return row


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
    server, engine, _, name = serve.build(cell, args.seed)
    timeout_s = cell["config"]["server"]["request_timeout_ms"] / 1e3
    port = server.start()
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        rows.append(one_rate(port, engine, name, cell, args.seed, rate,
                             args.seconds, timeout_s))
        print("sweep: " + " ".join("%s=%s" % (k, _fmt(v))
                                   for k, v in rows[-1].items()), flush=True)
    server.stop(drain=False)
    good = [r["rate"] for r in rows if r["sustained"]]
    print("sweep: highest sustained rate %s requests/s; a cell below it "
          "takes 0.8 x that" % (max(good) if good else "none of these"))
    return 0


def _fmt(v):
    return "%.2f" % v if isinstance(v, float) else str(v)


if __name__ == "__main__":
    sys.exit(main())
