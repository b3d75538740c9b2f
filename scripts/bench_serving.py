"""Serving marshalling microbenchmark: columnar vs per-row paths.

Round 1's serving marshalling was row-at-a-time python (per-record list
building on input, `.tolist()` row boxing on output — VERDICT weak #4);
round 2 made `pipeline._run_saved_model` columnar (pack_records on
input, numpy row views on output).  This bench isolates exactly those
two marshalling stages at the VERDICT's target shape (4096-wide MLP
output), then shows the end-to-end partition serving for context.  It
runs on CPU: it prices host marshalling, and a device transfer would
only add to both sides.

    python scripts/bench_serving.py [--rows 4096] [--batch 256] [--width 4096]
"""
import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _time(fn, repeats):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_input_marshalling(rows, tensor_names, repeats):
    """rows -> feed-ready numpy columns: per-column comprehension (round 1)
    vs pack_records (round 2)."""
    from tensorflowonspark_tpu import marker

    def row_path():
        cols = {name: np.asarray([rec[i] for rec in rows], np.float32)
                for i, name in enumerate(tensor_names)}
        return cols

    def col_path():
        packed = marker.pack_records(rows)
        assert isinstance(packed, marker.PackedChunk)
        return dict(zip(tensor_names, packed.columns))

    np.testing.assert_array_equal(row_path()["x"], col_path()["x"])
    return _time(row_path, repeats), _time(col_path, repeats)


def bench_output_marshalling(out, repeats):
    """[N, W] output array -> per-row results: zip(*tolist) boxing
    (round 1) vs numpy row views (round 2)."""
    def row_path():
        return [row for row in zip(*(p.tolist() for p in (out,)))]

    def col_path():
        return list(iter(out))

    a, b = row_path()[7], col_path()[7]
    np.testing.assert_allclose(a[0], np.asarray(b), rtol=0)
    return _time(row_path, repeats), _time(col_path, repeats)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    W, N = args.width, args.rows
    rows = [(rng.standard_normal(W).astype(np.float32),) for _ in range(N)]
    out = rng.standard_normal((N, W)).astype(np.float32)

    t_in_row, t_in_col = bench_input_marshalling(rows, ["x"], args.repeats)
    t_out_row, t_out_col = bench_output_marshalling(out, args.repeats)
    print(f"input marshalling  ({N} rows x {W} f32): "
          f"row-path {t_in_row * 1e3:7.1f} ms  columnar {t_in_col * 1e3:7.1f} ms "
          f"-> {t_in_row / t_in_col:5.1f}x")
    print(f"output marshalling ({N} rows x {W} f32): "
          f"row-path {t_out_row * 1e3:7.1f} ms  columnar {t_out_col * 1e3:7.1f} ms "
          f"-> {t_out_row / t_out_col:5.1f}x")
    total_row = t_in_row + t_out_row
    total_col = t_in_col + t_out_col
    print(f"marshalling total: {total_row / total_col:5.1f}x "
          f"({total_row * 1e3:.1f} -> {total_col * 1e3:.1f} ms)")

    # end-to-end partition serving for context (includes the W x W matmul,
    # which dominates on CPU — the marshalling delta rides on top)
    import tempfile

    import jax  # noqa: F401

    from tensorflowonspark_tpu import export, pipeline

    tmp = tempfile.mkdtemp()
    export_dir = os.path.join(tmp, "mlp")
    from tensorflowonspark_tpu.models.linear import MLP

    model = MLP(features=[W])
    params = model.init(jax.random.key(0),
                        np.zeros((1, W), "float32"))["params"]
    export.export_saved_model(
        export_dir, params,
        builder="tensorflowonspark_tpu.models.linear:MLP",
        builder_kwargs={"features": [W]},
        signatures={"serving_default": {
            "inputs": {"x": {"shape": [W], "dtype": "float32"}},
            "outputs": ["y"]}})
    run_fn = pipeline._run_saved_model(export_dir, None, args.batch,
                                       None, None)
    list(run_fn(iter(rows[:args.batch])))   # compile
    t_e2e = _time(lambda: list(run_fn(iter(rows))), args.repeats)
    print(f"end-to-end columnar serving: {t_e2e:.3f}s "
          f"({N / t_e2e:,.0f} rows/s incl. {W}x{W} matmul on CPU)")


if __name__ == "__main__":
    main()
