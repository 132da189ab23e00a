# Native components: RecordIO library (ctypes-loaded by the Python io
# pipeline) and data packing tools. Parity targets: the reference's
# Makefile builds libcxxnet wrappers + im2bin/im2rec tools
# (/root/reference/Makefile:1-160).

CXX ?= g++
CXXFLAGS = -O3 -fPIC -std=c++17 -Wall
OPENCV_CFLAGS := $(shell pkg-config --cflags opencv4 2>/dev/null)
OPENCV_LIBS := $(shell pkg-config --libs opencv4 2>/dev/null)

PY_CFLAGS := $(shell python3-config --includes 2>/dev/null)
PY_LIBDIR := $(shell python3 -c "import sysconfig; print(sysconfig.get_config_var('LIBDIR'))" 2>/dev/null)
PY_VER := $(shell python3 -c "import sysconfig; print(sysconfig.get_config_var('LDVERSION'))" 2>/dev/null)

LIB = lib/libcxxnet_io.so
WRAPLIB = lib/libcxxnet_wrapper.so
TOOLS = bin/im2rec bin/rec2idx bin/im2bin bin/bin2rec

# the Python-embedding wrapper needs python3 dev headers; skip when absent
ifneq ($(PY_CFLAGS),)
all: $(LIB) $(TOOLS) $(WRAPLIB)
else
all: $(LIB) $(TOOLS)
endif

lib bin:
	mkdir -p $@

$(LIB): src/io/recordio.cc src/io/recordio.h | lib
	$(CXX) $(CXXFLAGS) -shared -o $@ src/io/recordio.cc

$(WRAPLIB): wrapper/cxxnet_wrapper.cc wrapper/cxxnet_wrapper.h | lib
	$(CXX) $(CXXFLAGS) $(PY_CFLAGS) -shared -o $@ \
		wrapper/cxxnet_wrapper.cc \
		-L$(PY_LIBDIR) -Wl,-rpath,$(PY_LIBDIR) -lpython$(PY_VER) -ldl

bin/im2rec: tools/im2rec.cc src/io/recordio.cc src/io/recordio.h | bin
	$(CXX) $(CXXFLAGS) $(OPENCV_CFLAGS) -o $@ tools/im2rec.cc \
		src/io/recordio.cc $(OPENCV_LIBS)

bin/rec2idx: tools/rec2idx.cc src/io/recordio.cc src/io/recordio.h | bin
	$(CXX) $(CXXFLAGS) -o $@ tools/rec2idx.cc src/io/recordio.cc

bin/im2bin: tools/im2bin.cc src/io/binpage.h | bin
	$(CXX) $(CXXFLAGS) -o $@ tools/im2bin.cc

bin/bin2rec: tools/bin2rec.cc src/io/binpage.h src/io/recordio.cc \
		src/io/recordio.h | bin
	$(CXX) $(CXXFLAGS) -o $@ tools/bin2rec.cc src/io/recordio.cc

# smoke for the Matlab mex wrapper: no Matlab in CI, so a functional
# stub mex.h/mxArray stands in for $(MATLAB)/extern (catches
# syntax/type/symbol errors; a real build just swaps the include path)
mex-smoke: lib/cxxnet_mex_smoke.so
lib/cxxnet_mex_smoke.so: wrapper/matlab/cxxnet_mex.cpp \
		wrapper/matlab/mex_stub/mex.h \
		wrapper/matlab/mex_stub/mex_stub.cc \
		wrapper/cxxnet_wrapper.h | lib
	$(CXX) $(CXXFLAGS) -Iwrapper/matlab/mex_stub -shared -o $@ \
		wrapper/matlab/cxxnet_mex.cpp \
		wrapper/matlab/mex_stub/mex_stub.cc

# C host that EXECUTES the mex dispatch table against the functional
# stub + the real embedded-CPython wrapper lib (the CI stand-in for
# running example.m inside Matlab)
mex-driver: bin/mex_driver
bin/mex_driver: wrapper/matlab/mex_driver.cc \
		wrapper/matlab/cxxnet_mex.cpp \
		wrapper/matlab/mex_stub/mex.h \
		wrapper/matlab/mex_stub/mex_stub.cc \
		wrapper/cxxnet_wrapper.h $(WRAPLIB) | bin
	$(CXX) $(CXXFLAGS) -Iwrapper/matlab/mex_stub -o $@ \
		wrapper/matlab/mex_driver.cc \
		wrapper/matlab/cxxnet_mex.cpp \
		wrapper/matlab/mex_stub/mex_stub.cc \
		-Llib -Wl,-rpath,$(abspath lib) -lcxxnet_wrapper

# ---- release bar -----------------------------------------------------
# `make check` is the FULL suite in one process, including the `slow`
# e2e accuracy gates (MNIST MLP, two MNIST conv gates, BN/concat
# inception held-out gates); `make check-fast` skips only the MNIST e2e
# gates. What the driver holds a PR to is tier-1 (README.md "Testing").
check: all
	python -m pytest tests/ -q

check-fast: all
	python -m pytest tests/ -q --ignore=tests/test_mnist_e2e.py

# cxxlint: the framework-aware static-analysis suite
# (doc/static_analysis.md). Exit 0 clean / 1 findings / 2 usage; also
# enforced inside tier-1 by tests/test_lint.py::test_tree_is_lint_clean.
lint:
	python -m cxxnet_tpu.lint cxxnet_tpu/ tools/ --format json

clean:
	rm -rf lib bin

.PHONY: all clean mex-smoke mex-driver check check-fast lint
