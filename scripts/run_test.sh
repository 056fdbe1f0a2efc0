#!/usr/bin/env bash
# Test runner (reference scripts/run_test.sh parity): pytest per file for
# leaked-state hygiene, CPU-forced virtual 8-device mesh.
set -u
cd "$(dirname "$0")/.."
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
failed=0
echo "=== vescale-lint (static analysis gate)"
python -m vescale_tpu.analysis --strict lint || failed=1
# Every scripts/*_smoke.py (the multi-process rigs: the only coverage of two
# real processes) runs ONCE, through the tests/test_*.py that spawns it, in
# the loop below.
echo "=== what-if CLI smoke (audited (dp,tp,pp) re-scoring)"
python -m vescale_tpu.analysis whatif --devices 8 --top 3 || failed=1
for f in tests/test_*.py; do
  echo "=== $f"
  python -m pytest "$f" -q || failed=1
done
exit $failed
