"""Native C++ data-plane tests: build, gather/scale correctness, prefetcher."""

import numpy as np
import pytest

from distkeras_tpu.data.native_loader import gather_rows, get_lib, scale_f32
from distkeras_tpu.data.prefetch import RoundFeeder


def test_native_lib_builds():
    lib = get_lib()
    assert lib is not None, "g++ toolchain present in this image; build must succeed"


def test_gather_rows_matches_numpy():
    rng = np.random.default_rng(0)
    src = rng.normal(size=(1000, 17)).astype(np.float32)
    idx = rng.integers(0, 1000, size=(4, 3, 5))
    np.testing.assert_array_equal(gather_rows(src, idx), src[idx])


def test_gather_rows_multidim_rows_and_int_dtype():
    rng = np.random.default_rng(1)
    src = rng.integers(0, 100, size=(50, 4, 4)).astype(np.int32)
    idx = rng.integers(0, 50, size=(7,))
    np.testing.assert_array_equal(gather_rows(src, idx), src[idx])


def test_gather_rows_out_of_range_raises():
    if get_lib() is None:
        pytest.skip("native lib unavailable")
    src = np.zeros((10, 3), np.float32)
    with pytest.raises(IndexError):
        gather_rows(src, np.array([0, 99]))


def test_scale_f32_matches_numpy():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(333, 7)).astype(np.float32)
    np.testing.assert_allclose(scale_f32(x, 0.5, 2.0), (x - 0.5) * 2.0, rtol=1e-6)


def test_scale_f32_bias_matches_numpy():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(257, 3)).astype(np.float32)
    got = scale_f32(x, 0.25, 3.0, bias=-1.5)
    np.testing.assert_allclose(got, (x - 0.25) * 3.0 + (-1.5), rtol=1e-6)


def test_scale_f32_bias_exact_at_range_endpoints():
    # The endpoints of a min-max rescale must be hit EXACTLY: (i_min - i_min) *
    # scale + o_min == o_min in float arithmetic regardless of scale magnitude.
    # (This is the catastrophic-cancellation case the separate bias exists for.)
    x = np.array([2.0, 2.0 + 1e-6], np.float32)
    scale = 1.0 / float(x[1] - x[0])  # huge scale from a degenerate range
    out = scale_f32(x, float(x[0]), scale, bias=5.0)
    assert out[0] == np.float32(5.0)


def test_native_abi_version_pinned_to_source():
    # The ctypes declarations are only valid for the exact C signatures they
    # were written against. dk_abi_version() pins them: this test fails if
    # loader.cc's version constant and the Python _ABI_VERSION ever diverge
    # (i.e. someone changed a signature on one side only).
    import re

    from distkeras_tpu.data import native_loader

    src = open(native_loader._SRC).read()
    m = re.search(r"int\s+dk_abi_version\(\)\s*\{\s*return\s+(\d+)\s*;", src)
    assert m, "loader.cc must define dk_abi_version()"
    assert int(m.group(1)) == native_loader._ABI_VERSION, (
        "native ABI version mismatch between loader.cc and native_loader.py — "
        "a signature changed on one side only"
    )
    lib = get_lib()
    if lib is not None:
        assert lib.dk_abi_version() == native_loader._ABI_VERSION


def test_min_max_semantics_through_native_path():
    # End-to-end guard for the data plane: MinMaxTransformer output must map
    # [i_min, i_max] -> [o_min, o_max] with exact endpoints via the native path.
    from distkeras_tpu.data import DataFrame
    from distkeras_tpu.data.transformers import MinMaxTransformer

    x = np.array([[0.0], [255.0], [51.0]], np.float32)
    df = DataFrame({"features": x})
    out = MinMaxTransformer(o_min=-1.0, o_max=1.0).transform(df)["features_normalized"]
    assert out[0, 0] == np.float32(-1.0)
    assert out[1, 0] == np.float32(1.0)
    np.testing.assert_allclose(out[2, 0], -1.0 + 2.0 * 51.0 / 255.0, rtol=1e-6)


def test_batch_plan_uses_gather(tmp_path):
    from distkeras_tpu.data import DataFrame, make_batches

    rng = np.random.default_rng(3)
    df = DataFrame({"features": rng.normal(size=(96, 5)).astype(np.float32),
                    "label": rng.integers(0, 3, size=96).astype(np.int32)})
    plan = make_batches(df, "features", "label", batch_size=4, num_workers=2,
                        window=3, shuffle=True, seed=7)
    fx, fy = plan.round(0)
    idx = plan.index[0]
    np.testing.assert_array_equal(fx, df["features"][idx])
    np.testing.assert_array_equal(fy, df["label"][idx])


def test_round_feeder_order_and_completion():
    staged = []
    feeder = RoundFeeder(5, lambda r: (staged.append(r), r * 10)[1], start_round=1)
    seen = list(feeder)
    assert seen == [(1, 10), (2, 20), (3, 30), (4, 40)]
    assert staged == [1, 2, 3, 4]


def test_round_feeder_propagates_errors():
    def stage(r):
        if r == 2:
            raise RuntimeError("boom")
        return r

    feeder = RoundFeeder(5, stage)
    with pytest.raises(RuntimeError, match="boom"):
        list(feeder)


def test_round_feeder_abandonment_stops_thread():
    """A consumer that dies mid-loop (OOM, a lost device) must not leave the
    feeder thread blocked on Queue.put holding staged batches forever."""
    import time
    import weakref

    class Batch:  # stand-in for a staged device array
        pass

    alive = []

    def stage(r):
        b = Batch()
        alive.append(weakref.ref(b))
        return b

    feeder = RoundFeeder(1000, stage, depth=2)

    def consume_then_die():
        for r, batch in feeder:
            if r == 3:
                raise RuntimeError("simulated mid-training failure")

    with pytest.raises(RuntimeError, match="mid-training"):
        consume_then_die()
    deadline = time.time() + 5
    while feeder._thread.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not feeder._thread.is_alive(), "feeder thread leaked"
    # every staged batch the consumer never took has been dropped
    import gc

    gc.collect()
    assert all(ref() is None for ref in alive)


def test_round_feeder_close_idempotent_before_and_after_use():
    feeder = RoundFeeder(3, lambda r: r)
    assert list(feeder) == [(0, 0), (1, 1), (2, 2)]
    feeder.close()
    feeder.close()
