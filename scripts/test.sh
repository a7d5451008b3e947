#!/usr/bin/env sh
# Tier-1 gate (ROADMAP.md): every PR runs exactly the pytest line below.
set -eu
cd "$(dirname "$0")/.."

# Stage 0: lint (`make lint`, ruff config in pyproject.toml). Blocking when
# ruff is installed; `make lint` itself skips gracefully when it is not
# (the container has no network for installs).
make lint

# Stage 1 (blocking): the tier-1 pytest gate.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"

# Stage 2 (non-blocking): the benchmark harness + regression check
# (`make bench`). A perf regression or harness breakage warns loudly but
# does not fail the gate — the blocking regression gate is `make bench`
# itself. Skip with REPRO_BENCH=0 (e.g. quick local iterations).
if [ "${REPRO_BENCH:-1}" != "0" ]; then
    if ! make bench; then
        echo "WARNING: benchmark stage failed or regressed (non-blocking;" \
             "run 'make bench' for details)" >&2
    fi
fi

# Stage 3 (non-blocking): the continuous-batching serving engine over a
# tiny synthetic trace (`make serve-smoke`) — catches engine/CLI breakage
# the unit suite might miss. Skip with REPRO_SERVE=0.
if [ "${REPRO_SERVE:-1}" != "0" ]; then
    if ! make serve-smoke; then
        echo "WARNING: serve-smoke stage failed (non-blocking; run" \
             "'make serve-smoke' for details)" >&2
    fi
fi

# Stage 4 (non-blocking): the multi-replica fleet smoke (`make
# fleet-smoke`: scripted drain/kill/rejoin over a 2-replica fleet) plus the
# slow randomized-trace fuzz (`pytest -m slow`; excluded from tier-1 by the
# pyproject addopts, and a no-op skip when hypothesis is absent). Skip with
# REPRO_FLEET=0.
if [ "${REPRO_FLEET:-1}" != "0" ]; then
    if ! make fleet-smoke; then
        echo "WARNING: fleet-smoke stage failed (non-blocking; run" \
             "'make fleet-smoke' for details)" >&2
    fi
    if ! make fuzz; then
        echo "WARNING: slow fuzz stage failed (non-blocking; run" \
             "'make fuzz' for details)" >&2
    fi
fi

# Stage 5 (non-blocking, opt-in): the Pallas kernel smoke (`make
# kernels-smoke`): the matmul/attention kernel suite plus the ring and
# chunk-pipelined fused collective kernels. Stage 1 already runs them (TPU
# interpret mode on CPU, Mosaic compiles for a described v5e), so this is
# an opt-in re-run of just those files. Enable with REPRO_KERNELS=1.
if [ "${REPRO_KERNELS:-0}" = "1" ]; then
    if ! make kernels-smoke; then
        echo "WARNING: kernels-smoke stage failed (non-blocking; run" \
             "'make kernels-smoke' for details)" >&2
    fi
fi

# Stage 6 (non-blocking): the runtime-health smoke (`make health-smoke`:
# scripted corrupt + stall comm faults with island guards and the health
# monitor on — exercises guard trips, quarantine, and backend demotion
# through the serve CLI). Skip with REPRO_HEALTH=0.
if [ "${REPRO_HEALTH:-1}" != "0" ]; then
    if ! make health-smoke; then
        echo "WARNING: health-smoke stage failed (non-blocking; run" \
             "'make health-smoke' for details)" >&2
    fi
fi
