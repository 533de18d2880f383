# Repo-level entry points. `make check` is the default: the native-library
# build (fails loudly when the toolchain is missing — a silent fallback to
# the pure-Python data plane is a 100x perf bug that looks like a pass),
# then the static-analysis gate, the seven pytest gates (serving, out-of-core
# data plane, kernels, observability, fleet, programs, memory) and the
# obs-report smoke — see tools/Makefile for the individual targets. None of
# them times anything: speed is read on the chip by benchmarks/
# (BENCHMARK.json, PERF.md).

.DEFAULT_GOAL := check

check: native
	$(MAKE) -C tools check

# both native IO libraries (libmarlin_textio.so, libmarlin_chunkstore.so)
native:
	$(MAKE) -C marlin_tpu/native

serve-gate:
	$(MAKE) -C tools serve-gate

ooc-gate:
	$(MAKE) -C tools ooc-gate

obs-gate:
	$(MAKE) -C tools obs-gate

fleet-gate:
	$(MAKE) -C tools fleet-gate

# repo-aware static analysis (tools/analyze; docs/static_analysis.md):
#   make analyze / make analyze-gate
#   make analyze BASELINE=update REASON='why'
analyze:
	$(MAKE) -C tools analyze

analyze-gate:
	$(MAKE) -C tools analyze-gate

# build the .mchunk sidecar for a data file (native binary data plane):
#   make chunkstore SRC=path/to/matrix.txt
# auto-detects text vs idx3 from the name; more knobs via
#   python -m marlin_tpu.io.chunkstore build --help
chunkstore: native
	@test -n "$(SRC)" || { echo "usage: make chunkstore SRC=<file>"; exit 2; }
	env JAX_PLATFORMS=cpu python -c "\
	from marlin_tpu.io.chunkstore import _main; \
	import sys; sys.exit(_main(['build', '$(SRC)']))"

tier1:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

.PHONY: check native serve-gate ooc-gate obs-gate analyze analyze-gate \
	chunkstore tier1
