# Developer entry points. `make test` is the tier-1 gate from ROADMAP.md.
PY ?= python

.PHONY: test test-full lint bench bench-baseline calibrate quickstart deps \
        serve-smoke fleet-smoke health-smoke kernels-smoke chip-smoke fuzz

deps:
	$(PY) -m pip install -r requirements.txt

lint:               # ruff gate (config in pyproject.toml); skips when ruff
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
	    $(PY) -m ruff check src tests benchmarks scripts examples; \
	else \
	    echo "lint: ruff not installed — skipping (pip install ruff)"; \
	fi

test:
	./scripts/test.sh

test-full:          # no -x: full failure report
	PYTHONPATH=src $(PY) -m pytest -q

bench:              # harness (CSV + BENCH_comms.json) then schema/regression gate
	PYTHONPATH=src $(PY) -m benchmarks.run --json BENCH_comms.json
	PYTHONPATH=src $(PY) scripts/check_bench.py BENCH_comms.json \
	    --baseline benchmarks/BENCH_baseline.json

bench-baseline:     # accept the current numbers as the new checked-in baseline
	PYTHONPATH=src $(PY) -m benchmarks.run --json benchmarks/BENCH_baseline.json

calibrate:          # measure this machine into the autotune cache
	PYTHONPATH=src $(PY) -m repro.autotune calibrate

serve-smoke:        # continuous-batching engine over a tiny synthetic trace
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
	    $(PY) -m repro.launch.serve --arch tinyllama-1.1b --reduced \
	    --mode continuous --mesh-shape 1 8 --requests 6 --tokens 4 \
	    --max-batch 4 --prefill-batch 2 --bucket-edges 8 16 \
	    --comm-policy auto

fleet-smoke:        # 2-replica fleet with a scripted kill + rejoin
	PYTHONPATH=src $(PY) -m repro.launch.serve --arch tinyllama-1.1b \
	    --reduced --mode continuous --replicas 2 --router least-loaded \
	    --fault-plan "drain:1@1 kill:1@3 rejoin:1@5" \
	    --ckpt-dir /tmp/repro-fleet-ckpt --requests 8 --tokens 4 \
	    --max-batch 4 --prefill-batch 2 --bucket-edges 8 16

health-smoke:       # scripted comm faults: guards + monitor + quarantine
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
	    $(PY) -m repro.launch.serve --arch tinyllama-1.1b --reduced \
	    --mode continuous --mesh-shape 1 8 --comm-backend ring \
	    --island-guards --health-monitor \
	    --comm-fault-plan "corrupt:mlp@1 stall:mlp@3x4" \
	    --requests 8 --tokens 4 --max-batch 4 --prefill-batch 2 \
	    --bucket-edges 8

kernels-smoke:      # Pallas kernel suites incl. the chunk-pipelined fused
	            # collectives: TPU interpret mode on CPU, plus Mosaic
	            # compiles of the fused kernels for a described v5e 2x2
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    PYTHONPATH=src $(PY) -m pytest -q -rs tests/test_kernels.py \
	    tests/test_pk_comm.py tests/test_fused_chunks.py \
	    tests/test_tpu_compile.py

chip-smoke:         # tinyllama-1.1b at full width on one TPU (fails off-TPU)
	$(PY) chip_smoke.py

fuzz:               # slow randomized/property tests (uses hypothesis if installed)
	PYTHONPATH=src $(PY) -m pytest -q -m slow tests/test_property.py

quickstart:
	PYTHONPATH=src $(PY) examples/quickstart.py
