#!/usr/bin/env bash
# CI entry (reference paddle/scripts/paddle_build.sh: build, ctest, python
# unittests, API-diff gate). Builds the native runtime, runs the tier-1 pytest
# command on a virtual 8-device CPU mesh (tests/conftest.py sets the mesh), and
# then the slow tier the driver leaves out, and regenerates+diffs the API spec.
# Everything else CI once smoked inline is a test under tests/ (CHANGES.md,
# PR 29, has the stage -> test table).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== build native runtime =="
python -c "from paddle_tpu import native; native.lib(); print('native runtime built:', native._lib_path())"

echo "== python unittests (tier-1: not slow, six workers, one file a worker at a time) =="
# One run, no shards and no retry: the driver's run of this command reaches
# its end in about three minutes. The limit is the driver's.
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
    --dist loadfile -p no:randomly

echo "== python unittests (slow tier: soaks and fresh-process cache checks; about a minute) =="
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slow -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile

echo "== API diff gate =="
python tools/print_signatures.py > "${TMPDIR:-/tmp}/API.spec.current"
diff -u paddle_tpu/API.spec "${TMPDIR:-/tmp}/API.spec.current" \
    || { echo "API surface changed; regenerate paddle_tpu/API.spec"; exit 1; }
echo "ALL GREEN"
