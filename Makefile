.PHONY: native native-cmake native-cc test check clean postmortem-demo

# Build the native core. Prefers the CMake/Ninja build (full configure
# checks, separate bench/test binaries); falls back to a plain
# compiler-driver build of just libtpucoll.so when cmake is not
# installed, so `pip install .` / `make native` work on minimal images.
# SANITIZE=address|thread|undefined always takes the fallback path: sanitizer
# flavors are a test-rig artifact of this cmake-less build (the cmake
# build has TPUCOLL_OUTPUT_DIR for the same isolation).
native:
	@if [ -n "$(SANITIZE)" ]; then \
		$(MAKE) -j$$(nproc) native-cc; \
	elif command -v cmake >/dev/null 2>&1 && command -v ninja >/dev/null 2>&1; then \
		$(MAKE) native-cmake; \
	else \
		$(MAKE) -j$$(nproc) native-cc; \
	fi

native-cmake:
	cmake -S csrc -B build -G Ninja -DCMAKE_BUILD_TYPE=RelWithDebInfo >/dev/null
	cmake --build build

# ---- fallback build (no cmake): mirrors csrc/CMakeLists.txt ----
CXX ?= g++

# Sanitizer flavors: `make SANITIZE=address` (or thread, undefined)
# compiles the whole core with -fsanitize=... into its own build dir and
# a SUFFIXED library (libtpucoll_asan.so / libtpucoll_tsan.so /
# libtpucoll_ubsan.so) so instrumented builds never clobber — or get
# clobbered by — the production .so.
# Run the Python suite against one with
#   TPUCOLL_LIB=$PWD/gloo_tpu/_native/libtpucoll_asan.so \
#   TPUCOLL_SKIP_BUILD=1 python -m pytest tests/ ...
# (tests/test_native_unit.py has a skip-unless-built ASan smoke test).
SAN_SUFFIX :=
SAN_FLAGS :=
ifeq ($(SANITIZE),address)
SAN_SUFFIX := _asan
SAN_FLAGS := -fsanitize=address -fno-omit-frame-pointer
else ifeq ($(SANITIZE),thread)
SAN_SUFFIX := _tsan
# tsan_preinclude.h: gcc-10 libtsan can't see pthread_cond_clockwait,
# which libstdc++-10 uses for timed condvar waits — without this every
# such mutex false-positives as "double lock" (GCC PR98624).
SAN_FLAGS := -fsanitize=thread -fno-omit-frame-pointer \
	-include csrc/tpucoll/common/tsan_preinclude.h
else ifeq ($(SANITIZE),undefined)
SAN_SUFFIX := _ubsan
# -fno-sanitize-recover=all: a UB report aborts the process instead of
# printing and carrying on, so the smoke test fails on the FIRST hit.
SAN_FLAGS := -fsanitize=undefined -fno-sanitize-recover=all \
	-fno-omit-frame-pointer
else ifneq ($(SANITIZE),)
$(error SANITIZE must be 'address', 'thread' or 'undefined', got '$(SANITIZE)')
endif

FB_BUILD := build-fb$(subst _,-,$(SAN_SUFFIX))
FB_LIB := gloo_tpu/_native/libtpucoll$(SAN_SUFFIX).so
FB_SRCS := $(filter-out csrc/tpucoll/common/crypto_avx512.cc,\
	$(wildcard csrc/tpucoll/*.cc csrc/tpucoll/*/*.cc))
FB_OBJS := $(patsubst csrc/%.cc,$(FB_BUILD)/%.o,$(FB_SRCS))
# -MMD/-MP: header dependency tracking, so editing a .h rebuilds the
# objects that include it (cmake gets this for free; the fallback must
# not silently package a stale .so after header edits).
FB_FLAGS := -std=c++17 -O3 -g -fPIC -Wall -Wextra -Icsrc -pthread -MMD -MP \
	$(SAN_FLAGS)

ARCH := $(shell uname -m)
ifeq ($(ARCH),x86_64)
FB_FLAGS += -mavx2 -mfma -mf16c
# AVX-512 ChaCha20 tier: own TU with -mavx512f, runtime-dispatched
# (crypto.cc), only when the compiler supports the flag.
FB_AVX512 := $(shell echo 'int main(){return 0;}' | $(CXX) -mavx512f \
	-x c++ - -o /dev/null 2>/dev/null && echo 1)
endif
ifeq ($(FB_AVX512),1)
FB_FLAGS += -DTPUCOLL_HAVE_AVX512=1
FB_OBJS += $(FB_BUILD)/tpucoll/common/crypto_avx512.o
endif

# The cmake build also produces the native test binaries; the fallback
# builds them too (same objects, one extra link each) so the pytest
# wrappers in tests/test_native_unit.py run on cmake-less images instead
# of failing on a missing build/tpucoll_unit. Sanitizer flavors skip
# them: their pytest entry points are the LD_PRELOAD smokes, not these.
ifeq ($(SAN_SUFFIX),)
native-cc: $(FB_LIB) build/tpucoll_unit build/tpucoll_integration \
	build/tpucoll_bench
else
native-cc: $(FB_LIB)
endif

$(FB_LIB): $(FB_OBJS)
	@mkdir -p gloo_tpu/_native
	$(CXX) -shared $(SAN_FLAGS) -o $@ $(FB_OBJS) -lpthread -lrt

build/tpucoll_unit: $(FB_BUILD)/tests/unit_main.o $(FB_OBJS)
	@mkdir -p build
	$(CXX) -o $@ $^ -lpthread -lrt

build/tpucoll_integration: $(FB_BUILD)/tests/integration_main.o $(FB_OBJS)
	@mkdir -p build
	$(CXX) -o $@ $^ -lpthread -lrt

# The host plane's native benchmark CLI (csrc/benchmark/main.cc), a port
# of the reference's latency/bandwidth harness, smoked by
# tests/test_native_unit.py::test_bench_cli_smoke; the cmake build
# produces it as a first-class target, so the fallback must too.
build/tpucoll_bench: $(FB_BUILD)/benchmark/main.o $(FB_OBJS)
	@mkdir -p build
	$(CXX) -o $@ $^ -lpthread -lrt

$(FB_BUILD)/tpucoll/common/crypto_avx512.o: \
		csrc/tpucoll/common/crypto_avx512.cc
	@mkdir -p $(dir $@)
	$(CXX) $(FB_FLAGS) -mavx512f -c $< -o $@

$(FB_BUILD)/%.o: csrc/%.cc
	@mkdir -p $(dir $@)
	$(CXX) $(FB_FLAGS) -c $< -o $@

-include $(FB_OBJS:.o=.d) $(FB_BUILD)/tests/unit_main.d \
	$(FB_BUILD)/tests/integration_main.d $(FB_BUILD)/benchmark/main.d

test: native
	python -m pytest tests/ -x -q

# Static-analysis suite (docs/check.md): the project-native invariants —
# C-ABI mirroring, exception tightness, env hygiene, explicit atomics,
# flightrec coverage, metrics name agreement, lock-order discipline,
# no bare asserts. `make check JSON=report.json` also writes the
# machine-readable report CI annotations consume.
check:
	python -m tools.check $(if $(JSON),--json $(JSON))

# Post-mortem walkthrough (docs/flightrec.md): inject a stall with the
# fault plane, let the watchdog auto-dump the always-on flight recorder,
# provoke a schedule desync, then merge the per-rank dumps and print the
# blame — the whole chaos -> recorder -> merge -> blame chain.
postmortem-demo: native
	JAX_PLATFORMS=cpu python examples/example_flightrec.py

clean:
	rm -rf build build-fb build-fb-asan build-fb-tsan build-fb-ubsan \
		gloo_tpu/_native/*.so
