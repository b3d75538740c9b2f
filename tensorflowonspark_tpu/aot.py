"""AOT (ahead-of-time) compiled inference artifacts + the native PJRT runner.

The reference serves models from executor JVMs through the TF Java/JNI bridge
(reference: src/main/scala/com/yahoo/tensorflowonspark/TFModel.scala:24-29
SavedModelBundle cache, :245-292 Session.runner feed/fetch;
Inference.scala:52-79 CLI). The TPU-native equivalent serializes the jitted
forward function to **StableHLO** (via jax.export) at fixed serving batch
sizes and executes it through one of two engines:

- ``jax``  — deserialize + call in-process (always available);
- ``native`` — the C++ PJRT runner (native/pjrt_runner.cc) loaded over
  ctypes, which compiles the StableHLO against any PJRT plugin
  (libtpu.so on TPU hosts; the mock plugin in tests). This path needs NO
  Python model code at serving time — like the reference's JVM bundle.

Artifact layout under ``<export_dir>/aot/``:
  model_b{N}.jexport        jax.export serialized artifact (jax engine)
  model_b{N}.stablehlo.mlir StableHLO module text (native engine)
  compile_options.pb        serialized CompileOptionsProto (native engine)
  aot_spec.json             {batch_sizes, inputs, outputs, platforms}
"""
import ctypes
import json
import logging
import os

logger = logging.getLogger(__name__)

AOT_DIR = "aot"
SPEC_FILE = "aot_spec.json"
PLUGIN_ENV = "TFOS_TPU_PJRT_PLUGIN"

# numpy dtype name -> PJRT_Buffer_Type (pjrt_c_api.h PJRT_Buffer_Type enum)
_PJRT_DTYPE = {
    "bool": 1, "int8": 2, "int16": 3, "int32": 4, "int64": 5,
    "uint8": 6, "uint16": 7, "uint32": 8, "uint64": 9,
    "float16": 10, "float32": 11, "float64": 12, "bfloat16": 13,
}
_PJRT_DTYPE_INV = {v: k for k, v in _PJRT_DTYPE.items()}


# --------------------------------------------------------------------------
# Export
# --------------------------------------------------------------------------

def export_aot(export_dir, apply_fn, params, signature, batch_sizes=(1, 64),
               platforms=("cpu", "tpu"), matmul_precision=None):
    """Serialize ``apply_fn(params, *inputs)`` at fixed batch sizes.

    Params are closed over (baked into the module as constants) so the
    artifact is self-contained — the serving side needs no model code and no
    param files, mirroring the reference's SavedModelBundle.
    ``signature`` uses the export.py schema ({"inputs": {name: {"shape",
    "dtype"}}, "outputs": [...]}); shapes exclude the batch dim.

    ``matmul_precision`` ("highest"/"float32" etc.) pins the dot/conv
    precision INTO the artifact: TPU compilers lower default-precision
    f32 matmuls to bf16 passes, so an artifact exported without this
    only matches a float32 host reference to ~bf16 tolerance.

    One artifact is written PER platform (jax.export cross-lowers, so a CPU
    host can export for TPU serving): single-platform modules keep the plain
    ``main(inputs)`` calling convention the native PJRT runner expects
    (a combined multi-platform export would add a platform-index argument).
    """
    import jax
    import jax.numpy as jnp
    from jax import export as jexp

    aot_dir = os.path.join(export_dir, AOT_DIR)
    os.makedirs(aot_dir, exist_ok=True)

    def fn(*inputs):
        if matmul_precision is not None:
            with jax.default_matmul_precision(matmul_precision):
                return apply_fn(params, *inputs)
        return apply_fn(params, *inputs)

    platforms = list(platforms) if platforms else ["cpu", "tpu"]
    in_meta = list(signature["inputs"].items())
    written = []
    for bs in sorted(set(int(b) for b in batch_sizes)):
        args = [jnp.zeros((bs,) + tuple(int(d) for d in (meta.get("shape") or ())),
                          dtype=meta.get("dtype") or "float32")
                for _, meta in in_meta]
        for platform in platforms:
            exported = jexp.export(jax.jit(fn), platforms=[platform])(*args)
            base = os.path.join(aot_dir, f"model_b{bs}.{platform}")
            with open(base + ".jexport", "wb") as f:
                f.write(exported.serialize())
            with open(base + ".stablehlo.mlir", "w") as f:
                f.write(exported.mlir_module())
        written.append(bs)

    from jax._src import compiler

    opts = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    with open(os.path.join(aot_dir, "compile_options.pb"), "wb") as f:
        f.write(opts.SerializeAsString())

    spec = {
        "batch_sizes": written,
        "inputs": [{"name": n, "shape": list(m.get("shape") or ()),
                    "dtype": m.get("dtype") or "float32"} for n, m in in_meta],
        "outputs": signature.get("outputs", ["output"]),
        "platforms": platforms,
    }
    with open(os.path.join(aot_dir, SPEC_FILE), "w") as f:
        json.dump(spec, f, indent=2)
    logger.info("AOT-exported batch sizes %s to %s", written, aot_dir)
    return aot_dir


def has_aot(export_dir):
    return os.path.exists(os.path.join(export_dir, AOT_DIR, SPEC_FILE))


def read_spec(export_dir):
    with open(os.path.join(export_dir, AOT_DIR, SPEC_FILE)) as f:
        return json.load(f)


def _pick_batch_size(spec, requested=None):
    sizes = sorted(spec["batch_sizes"])
    if requested is None:
        return sizes[-1]
    for b in sizes:
        if b >= requested:
            return b
    return sizes[-1]


# --------------------------------------------------------------------------
# Native runner (ctypes over native/pjrt_runner.cc)
# --------------------------------------------------------------------------

class _TosBuffer(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("size_bytes", ctypes.c_longlong),
                ("dtype", ctypes.c_int),
                ("ndims", ctypes.c_int),
                ("dims", ctypes.c_longlong * 8)]


_runner_lib = None


def _load_runner_lib():
    global _runner_lib
    if _runner_lib is not None:
        return _runner_lib
    so = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "native", "libtos_pjrt.so")
    if not os.path.exists(so):
        raise FileNotFoundError(
            f"{so} not built; run `make -C native` (needs the PJRT C API "
            "header from the tensorflow wheel)")
    lib = ctypes.CDLL(so)
    lib.tos_runner_create.restype = ctypes.c_void_p
    lib.tos_runner_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                      ctypes.c_int]
    try:
        lib.tos_runner_create_opts.restype = ctypes.c_void_p
        lib.tos_runner_create_opts.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int]
        lib.tos_has_create_opts = True
    except AttributeError:
        # a libtos_pjrt.so built before the create-options extension:
        # still fully usable for optionless plugins (libtpu, the mock)
        lib.tos_has_create_opts = False
    lib.tos_runner_destroy.argtypes = [ctypes.c_void_p]
    lib.tos_runner_device_count.argtypes = [ctypes.c_void_p]
    lib.tos_runner_device_count.restype = ctypes.c_int
    lib.tos_runner_platform.argtypes = [ctypes.c_void_p]
    lib.tos_runner_platform.restype = ctypes.c_char_p
    lib.tos_runner_compile.restype = ctypes.c_void_p
    lib.tos_runner_compile.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_longlong, ctypes.c_char_p, ctypes.c_int]
    lib.tos_exec_destroy.argtypes = [ctypes.c_void_p]
    lib.tos_exec_num_outputs.argtypes = [ctypes.c_void_p]
    lib.tos_exec_num_outputs.restype = ctypes.c_int
    lib.tos_exec_run.restype = ctypes.c_int
    lib.tos_exec_run.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_TosBuffer), ctypes.c_int,
        ctypes.POINTER(_TosBuffer), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p, ctypes.c_int]
    lib.tos_free.argtypes = [ctypes.c_void_p]
    _runner_lib = lib
    return lib


def default_plugin_path():
    """The PJRT plugin to execute against: $TFOS_TPU_PJRT_PLUGIN, else
    libtpu from the installed wheel."""
    env = os.environ.get(PLUGIN_ENV)
    if env:
        return env
    try:
        import libtpu

        return os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    except ImportError:
        raise FileNotFoundError(
            f"no PJRT plugin: set {PLUGIN_ENV} or install libtpu")


class NativeRunner:
    """One PJRT client + one compiled executable (per process, like the
    reference's per-executor-JVM session singleton)."""

    def __init__(self, mlir_text, compile_options, plugin_path=None,
                 create_options=None):
        """``create_options`` ({key: str|int}) are forwarded to
        PJRT_Client_Create as NamedValues — libtpu needs none; a
        proxying plugin may reject an optionless create."""
        self._lib = _load_runner_lib()
        plugin = plugin_path or default_plugin_path()
        err = ctypes.create_string_buffer(4096)
        opts = dict(create_options or {})
        if not getattr(self._lib, "tos_has_create_opts", False):
            if opts:
                raise RuntimeError(
                    "this libtos_pjrt.so predates create-option support; "
                    "rebuild it (`make -C native`) to pass create_options")
            self._runner = self._lib.tos_runner_create(
                plugin.encode(), err, len(err))
        else:
            n = len(opts)
            keys = (ctypes.c_char_p * n)()
            svals = (ctypes.c_char_p * n)()
            ivals = (ctypes.c_longlong * n)()
            kinds = (ctypes.c_int * n)()
            for i, (key, val) in enumerate(opts.items()):
                keys[i] = str(key).encode()
                if isinstance(val, (int, bool)):     # bools ride as int64
                    kinds[i], ivals[i], svals[i] = 1, int(val), b""
                else:
                    kinds[i], svals[i] = 0, str(val).encode()
            self._runner = self._lib.tos_runner_create_opts(
                plugin.encode(), keys, svals, ivals, kinds, n, err,
                len(err))
        if not self._runner:
            raise RuntimeError(f"PJRT client init failed: {err.value.decode()}")
        mlir = mlir_text.encode() if isinstance(mlir_text, str) else mlir_text
        self._exec = self._lib.tos_runner_compile(
            self._runner, mlir, len(mlir), compile_options,
            len(compile_options), err, len(err))
        if not self._exec:
            self._lib.tos_runner_destroy(self._runner)
            self._runner = None
            raise RuntimeError(f"PJRT compile failed: {err.value.decode()}")

    @property
    def platform(self):
        return self._lib.tos_runner_platform(self._runner).decode()

    @property
    def num_outputs(self):
        return self._lib.tos_exec_num_outputs(self._exec)

    def run(self, arrays):
        """Execute one batch: list of numpy arrays -> list of numpy arrays."""
        import numpy as np

        ins = (_TosBuffer * len(arrays))()
        keepalive = []
        for i, a in enumerate(arrays):
            a = np.ascontiguousarray(a)
            keepalive.append(a)
            if a.dtype.name not in _PJRT_DTYPE:
                raise TypeError(f"unsupported dtype {a.dtype}")
            ins[i].data = a.ctypes.data_as(ctypes.c_void_p)
            ins[i].size_bytes = a.nbytes
            ins[i].dtype = _PJRT_DTYPE[a.dtype.name]
            ins[i].ndims = a.ndim
            for d, s in enumerate(a.shape):
                ins[i].dims[d] = s
        max_out = max(self.num_outputs, 1)
        outs = (_TosBuffer * max_out)()
        n_out = ctypes.c_int(0)
        err = ctypes.create_string_buffer(4096)
        rc = self._lib.tos_exec_run(self._exec, ins, len(arrays), outs,
                                    max_out, ctypes.byref(n_out), err, len(err))
        if rc != 0:
            raise RuntimeError(f"PJRT execute failed: {err.value.decode()}")
        results = []
        for i in range(n_out.value):
            o = outs[i]
            dtype = np.dtype("uint16" if o.dtype == 13 else  # bf16 via uint16
                             _PJRT_DTYPE_INV[o.dtype])
            shape = tuple(o.dims[d] for d in range(o.ndims))
            buf = ctypes.string_at(o.data, o.size_bytes)
            self._lib.tos_free(o.data)
            arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
            if o.dtype == 13:  # upcast bf16 -> float32 for the caller
                arr = (arr.astype(np.uint32) << 16).view(np.float32)
            results.append(arr)
        return results

    def close(self):
        if getattr(self, "_exec", None):
            self._lib.tos_exec_destroy(self._exec)
            self._exec = None
        if getattr(self, "_runner", None):
            self._lib.tos_runner_destroy(self._runner)
            self._runner = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# Unified loading
# --------------------------------------------------------------------------

def _platform_artifact(aot_dir, bs, ext, want):
    """Pick the artifact for `want` platform, falling back to any present."""
    path = os.path.join(aot_dir, f"model_b{bs}.{want}.{ext}")
    if os.path.exists(path):
        return path
    import glob as glob_mod

    candidates = sorted(glob_mod.glob(
        os.path.join(aot_dir, f"model_b{bs}.*.{ext}")))
    if not candidates:
        raise FileNotFoundError(
            f"no AOT artifact model_b{bs}.*.{ext} under {aot_dir}")
    logger.warning("no %s artifact for platform %r; using %s", ext, want,
                   os.path.basename(candidates[0]))
    return candidates[0]


def load_aot(export_dir, batch_size=None, engine="auto", plugin_path=None,
             platform=None, create_options=None):
    """Return ``(predict, spec, bs)``: a fixed-batch predict(arrays)->arrays
    callable for the chosen engine, the artifact spec, and the compiled
    batch size (callers pad/split with `predict_batched`).

    engine: 'native' (C++ PJRT runner), 'jax' (in-process deserialize+call),
    or 'auto' (native if the runner lib + a plugin are available).
    ``platform`` picks the per-platform artifact; defaults to 'tpu' for the
    native engine (libtpu) and the current jax backend for the jax engine.
    ``create_options`` ({key: str|int}) forward to PJRT_Client_Create for
    plugins that require them (see NativeRunner).
    """
    spec = read_spec(export_dir)
    bs = _pick_batch_size(spec, batch_size)
    aot_dir = os.path.join(export_dir, AOT_DIR)

    if engine == "auto":
        try:
            _load_runner_lib()
            plugin_path = plugin_path or default_plugin_path()
            engine = "native"
        except (FileNotFoundError, OSError) as e:
            logger.info("native runner unavailable (%s); using jax engine", e)
            engine = "jax"

    if engine == "native":
        # libtpu serves the tpu-lowered artifact; any other plugin (a CPU
        # PJRT plugin, the test mock) gets the cpu lowering — tpu custom
        # calls would not compile there
        want = platform or ("tpu" if "libtpu" in (plugin_path or "") else "cpu")
        with open(_platform_artifact(aot_dir, bs, "stablehlo.mlir", want)) as f:
            mlir = f.read()
        with open(os.path.join(aot_dir, "compile_options.pb"), "rb") as f:
            copts = f.read()
        runner = NativeRunner(mlir, copts, plugin_path,
                              create_options=create_options)
        logger.info("native PJRT runner on platform %r (batch=%d)",
                    runner.platform, bs)

        def predict(arrays):
            return runner.run(arrays)

        predict.runner = runner
        return predict, spec, bs

    import jax
    from jax import export as jexp

    want = platform or jax.default_backend()
    with open(_platform_artifact(aot_dir, bs, "jexport", want), "rb") as f:
        exported = jexp.deserialize(f.read())

    def predict(arrays):
        out = exported.call(*arrays)
        return list(out) if isinstance(out, (tuple, list)) else [out]

    return predict, spec, bs


def predict_batched(predict, arrays, compiled_bs):
    """Run a variable-size batch through a fixed-batch predict by splitting
    into compiled_bs chunks and repeat-padding the tail (trimmed after)."""
    import numpy as np

    n = int(arrays[0].shape[0])
    outs_accum = None
    for start in range(0, n, compiled_bs):
        chunk = [a[start:start + compiled_bs] for a in arrays]
        got = chunk[0].shape[0]
        if got < compiled_bs:
            pad = compiled_bs - got
            chunk = [np.concatenate([c] + [c[-1:]] * pad, axis=0) for c in chunk]
        outs = predict(chunk)
        outs = [np.asarray(o)[:got] for o in outs]
        if outs_accum is None:
            outs_accum = [[o] for o in outs]
        else:
            for acc, o in zip(outs_accum, outs):
                acc.append(o)
    if outs_accum is None:
        return []
    return [np.concatenate(acc, axis=0) for acc in outs_accum]
