#!/usr/bin/env bash
# Black-box assertions against a RUNNING stack — role of the reference's
# scripts/service_regression_test.sh (string-compares CLI output incl.
# exact md5 handles).  Usage: ops/stack_smoke.sh [PORT]
set -euo pipefail
cd "$(dirname "$0")/.."
PORT="${1:-7025}"
CLI=(python -m das_tpu.service.client --port "$PORT")
# clients only: the service holds the chip, a client must never ask for it
export JAX_PLATFORMS=cpu

fail() { echo "SMOKE FAIL: $1" >&2; exit 1; }

expect() { # expect <label> <want> <got>
  [ "$3" = "$2" ] || fail "$1: want '$2', got '$3'"
  echo "ok: $1 = $2"
}

NAME="smoke_$RANDOM"
TOKEN=$("${CLI[@]}" create "$NAME" | grep -oE '[a-z]{20}' | head -1)
[ -n "$TOKEN" ] || fail "create returned no token"
echo "ok: create -> token"

# the checkpoint volume pre-loads the animals KB: counts with ZERO load RPCs
expect "count (checkpoint attach)" "(14, 26)" "$("${CLI[@]}" count "$TOKEN")"

# exact-handle assertions (reference service_regression_test.sh:24-38)
expect "query human->mammal" \
  "{{'\$1': 'bdfe4e7a431f73386f37c6448afe5840'}}" \
  "$("${CLI[@]}" query "$TOKEN" "Node n1 Concept human, Link Inheritance n1 \$1")"

GOT=$("${CLI[@]}" atom "$TOKEN" af12f10f9ae2002a1607ba0b47ba8407 --output-format DICT)
case "$GOT" in
  *"'name': 'human'"*) echo "ok: get_atom human dict" ;;
  *) fail "get_atom: unexpected '$GOT'" ;;
esac

# load RPC round trip on a second tenant (file:// source + status poll)
python - <<'EOF'
import os
import sys
sys.path.insert(0, ".")
from das_tpu.models.animals import write_animals_metta
os.makedirs("/tmp/das_stack_smoke", exist_ok=True)
write_animals_metta("/tmp/das_stack_smoke/animals.metta")
EOF
NAME2="smoke2_$RANDOM"
TOKEN2=$("${CLI[@]}" create "$NAME2" | grep -oE '[a-z]{20}' | head -1)
"${CLI[@]}" load "$TOKEN2" "file:///tmp/das_stack_smoke/animals.metta" >/dev/null
for _ in $(seq 1 20); do
  S=$("${CLI[@]}" status "$TOKEN2")
  [ "$S" = "Ready" ] && break
  sleep 1
done
expect "load->status" "Ready" "$S"
expect "count (loaded)" "(14, 26)" "$("${CLI[@]}" count "$TOKEN2")"

echo "STACK SMOKE PASS (port $PORT)"
