"""Test substrate: single-process simulated 8-device mesh.

TPU analog of the reference's "Spark local[*] mode is the fake cluster"
strategy (SURVEY.md §4): force 8 virtual CPU devices so shard_map/pjit
tests exercise the real collective code paths without hardware.  Must run
before jax initializes its backends, hence env mutation at conftest import.
"""

import itertools
import os

# Tests always run on the CPU backend; child processes (serving
# replicas, fleet hosts) inherit the variable.
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compilation cache under test: a run must not depend on
# what an earlier run left in the checkout's cache directory, six
# workers would write that one directory at once, and XLA:CPU logs an
# error line for every entry it loads.  The chip smoke is what
# exercises the cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# x64 available for finite-difference reference math; production arrays are
# created float32 explicitly, so float32 code paths are still what's tested.
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def sha256_of():
    """``sha256_of(leaves)``: one digest over the dtype, shape and bytes
    of every array, in order: how a test holds two builders to the same
    bytes."""
    import hashlib

    def digest(leaves):
        h = hashlib.sha256()
        for leaf in leaves:
            h.update(repr((leaf.dtype.str, leaf.shape)).encode())
            h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    return digest


@pytest.fixture
def spans_of(tmp_path):
    """``spans_of(fn, *args, **kwargs)`` calls ``fn`` under a telemetry
    session of its own in trace mode and returns (its result, the
    session's ``span`` events): how a test reads the stages of one call
    (a plan build's ``cache_hit``, its ``plan_cache_load``), which have
    no other record."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.utils.run_log import read_run_log

    calls = itertools.count()

    def run(fn, *args, **kwargs):
        out = tmp_path / f"spans_{next(calls)}"
        session = telemetry.start("trace", str(out))
        try:
            result = fn(*args, **kwargs)
        finally:
            session.close()
        return result, [e for e in read_run_log(str(out / "run_log.jsonl"))
                        if e["event"] == "span"]

    return run


@pytest.fixture
def hot_split(monkeypatch, sha256_of):
    """The planner's hot split under test (ISSUE 40):
    ``hot_split.calls`` lists the calls that reached the native
    library's column count and class split ("count", "split");
    ``hot_split.without("the_two_entries")`` takes both away, so
    ``np.bincount`` and ``_split_classes``' numpy body run beside the
    other native builders, ``hot_split.without("the_library")`` the
    whole library, as ``PHOTON_ML_TPU_NATIVE=0`` does;
    ``hot_split.same_bytes(a, b)`` holds two plans (pairs, lists of
    pairs) to one tree structure, every leaf to one dtype, shape and
    content, and both to one sha256."""
    import types

    import photon_ml_tpu.native as nat

    if not nat.native_available():
        pytest.skip("native library unavailable")
    real = {"count": nat.column_counts_native,
            "split": nat.split_classes_native}
    calls = []

    def recording(name):
        def entry(*args, **kwargs):
            built = real[name](*args, **kwargs)
            if built is not None:
                calls.append(name)
            return built
        return entry

    monkeypatch.setattr(nat, "column_counts_native", recording("count"))
    monkeypatch.setattr(nat, "split_classes_native", recording("split"))

    def without(what):
        if what == "the_library":
            monkeypatch.setenv("PHOTON_ML_TPU_NATIVE", "0")
            monkeypatch.setattr(nat, "_lib", False)
            assert nat.lib() is None
        else:
            assert what == "the_two_entries"
            for name in ("column_counts_native", "split_classes_native"):
                monkeypatch.setattr(nat, name, lambda *a, **k: None)

    def same_bytes(a, b):
        (leaves_a, structure_a), (leaves_b, structure_b) = (
            jax.tree_util.tree_flatten(tree) for tree in (a, b))
        assert structure_a == structure_b and leaves_a
        leaves_a = [np.asarray(leaf) for leaf in leaves_a]
        leaves_b = [np.asarray(leaf) for leaf in leaves_b]
        for x, y in zip(leaves_a, leaves_b):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert x.tobytes() == y.tobytes()
        assert sha256_of(leaves_a) == sha256_of(leaves_b)

    return types.SimpleNamespace(calls=calls, without=without,
                                 same_bytes=same_bytes)
