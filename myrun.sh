#!/bin/bash
# Launcher with the same shape as the reference's myrun.sh: one command,
# everything tee'd to raft.log.  A -backend=... flag selects the
# checker: the TPU-native engine (default) or stock TLC if tla2tools.jar is
# present.  All other flags pass through to the selected backend.
set -o pipefail
BACKEND=jax
CFG="${RAFT_CFG:-$(dirname "$0")/Raft.cfg}"
ARGS=()
for a in "$@"; do
  case "$a" in
    -backend=*) BACKEND="${a#-backend=}" ;;
    -config=*)  CFG="${a#-config=}" ;;
    *)          ARGS+=("$a") ;;
  esac
done
if [ "$BACKEND" = tlc ]; then
  # the reference path, verbatim semantics (requires tla2tools.jar + Raft.tla)
  exec java -Xms4g -Xmx12g -jar tla2tools.jar -deadlock -workers 4 \
    -config "$CFG" Raft.tla "${ARGS[@]}" 2>&1 | tee raft.log
else
  exec python -m tla_raft_tpu.check --config "$CFG" --log raft.log "${ARGS[@]}"
fi
