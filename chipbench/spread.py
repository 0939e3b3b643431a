"""The spread of each metric over a set of runs, as the builder's contract
defines it: the distance between the first and third quartile
(statistics.quantiles, n=4) as a share of the median.

    python3 -m chipbench.spread <file with one result line a run> ...

Each file is one set. Prints a set's median and spread for every metric, and
for each metric the widest spread over the sets and five times it (the rule
for a bound)."""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def read_set(path):
    by_metric = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith('{"correct"'):
                continue
            for name, m in json.loads(line)["metrics"].items():
                by_metric.setdefault(name, []).append(m["value"])
    return by_metric


def main(argv=None):
    widest = {}
    for path in (argv if argv is not None else sys.argv[1:]):
        for name, values in sorted(read_set(path).items()):
            if len(values) < 2:
                continue
            s = spread(values)
            widest[name] = max(widest.get(name, 0.0), s)
            print("%s %s n=%d median=%.6g spread=%.4f%% values=%s" % (
                path, name, len(values), statistics.median(values), 100 * s,
                " ".join("%.6g" % v for v in values)))
    for name, s in sorted(widest.items()):
        print("widest %s %.4f%% -> bound about %.4f" % (name, 100 * s, 5 * s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
