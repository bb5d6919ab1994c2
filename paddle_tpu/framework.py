"""Core framework state: dtypes, default device, RNG, global flags.

TPU-native rebuild of the reference's framework layer
(ref: python/paddle/base/framework.py, python/paddle/base/core dtype enum).
Instead of a C++ VarType enum we alias numpy/jax dtypes directly; instead of
CUDAPlace/CPUPlace device contexts we use jax devices and let XLA manage
streams.
"""
from __future__ import annotations

import contextlib
import os
import threading

# int64 / float64 parity with the reference requires x64 mode. All creation
# ops still default to float32 (see creation.py) so the TPU hot path never
# sees f64 unless the user asks for it.
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# ---------------------------------------------------------------------------
# dtypes (ref: paddle.float32 etc. map to VarType; here straight to numpy)
# ---------------------------------------------------------------------------
float16 = jnp.float16
bfloat16 = jnp.bfloat16
float32 = jnp.float32
float64 = jnp.float64
int8 = jnp.int8
int16 = jnp.int16
int32 = jnp.int32
int64 = jnp.int64
uint8 = jnp.uint8
uint16 = jnp.uint16
uint32 = jnp.uint32
uint64 = jnp.uint64
bool_ = jnp.bool_
complex64 = jnp.complex64
complex128 = jnp.complex128

_DTYPE_ALIASES = {
    "float16": float16, "fp16": float16, "half": float16,
    "bfloat16": bfloat16, "bf16": bfloat16,
    "float32": float32, "fp32": float32, "float": float32,
    "float64": float64, "fp64": float64, "double": float64,
    "int8": int8, "int16": int16, "int32": int32, "int64": int64,
    "uint8": uint8, "uint16": uint16, "uint32": uint32, "uint64": uint64,
    "bool": bool_, "complex64": complex64, "complex128": complex128,
}

FLOAT_DTYPES = (jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64)


def convert_dtype(dtype):
    """Normalise any dtype spec (str, np.dtype, jnp type) to a numpy dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise ValueError(f"unknown dtype {dtype!r}")
        dtype = _DTYPE_ALIASES[dtype]
    return np.dtype(dtype)


def is_floating_dtype(dtype) -> bool:
    return np.dtype(dtype) in (np.dtype(d) for d in FLOAT_DTYPES)


_state = threading.local()


def get_default_dtype():
    return getattr(_state, "default_dtype", np.dtype("float32"))


def set_default_dtype(dtype):
    _state.default_dtype = convert_dtype(dtype)


# ---------------------------------------------------------------------------
# global flags (ref: FLAGS_* gflags read by the C++ runtime)
# ---------------------------------------------------------------------------
_FLAGS = {
    "matmul_precision": "default",   # 'default' | 'high' | 'highest'
    "deterministic": False,
    "check_nan_inf": False,
}


def set_flags(flags: dict):
    for k, v in flags.items():
        key = k.replace("FLAGS_", "")
        if key not in _FLAGS:
            raise KeyError(f"unknown flag {k}")
        _FLAGS[key] = v
        if key == "matmul_precision":
            jax.config.update("jax_default_matmul_precision",
                              None if v == "default" else v)


def products_round_to_bfloat16():
    """The backend multiplies float32 operands as bfloat16, with float32
    accumulation: a TPU at the default matmul precision. Only here may a
    float32 weight be held in bfloat16 (`nlp.serving.hold_weights`): the
    products come out as the float32 ones would."""
    return (jax.default_backend() == "tpu"
            and jax.config.jax_default_matmul_precision in (None, "default"))


def get_flags(keys=None):
    if keys is None:
        return dict(_FLAGS)
    if isinstance(keys, str):
        keys = [keys]
    return {k: _FLAGS[k.replace("FLAGS_", "")] for k in keys}


# ---------------------------------------------------------------------------
# devices (ref: CPUPlace / CUDAPlace / XPUPlace -> jax devices)
# ---------------------------------------------------------------------------
class Place:
    def __init__(self, kind: str, index: int = 0):
        self.kind, self.index = kind, index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and (self.kind, self.index) == (other.kind, other.index))

    def __hash__(self):
        return hash((self.kind, self.index))


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(idx: int = 0):
    return Place("tpu", idx)


# alias so scripts written against the CUDA reference run unmodified
def CUDAPlace(idx: int = 0):
    return Place("tpu", idx)


def get_device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device):
    # Device selection is handled by JAX/PJRT at process start; accept and
    # validate for API parity.
    return get_device()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def disable_static(place=None):
    """ref: paddle.disable_static — enter dygraph. This framework is
    always dynamic-over-XLA, so this is a no-op kept for the countless
    reference scripts that call it at startup."""
    return None


def enable_static():
    """ref: paddle.enable_static — the static Program/Executor mode.
    Deliberately not supported (SURVEY §2.12 static shim): trace with
    @paddle.jit.to_static and export StableHLO via paddle.jit.save
    instead; paddle.static.InputSpec works unchanged."""
    raise NotImplementedError(
        "static-graph mode is not supported on the TPU backend. "
        "Migration: decorate with @paddle.jit.to_static (InputSpec "
        "supported) and use paddle.jit.save/load for deployment — "
        "see paddle_tpu.static for the shim and recipes.")


def device_count() -> int:
    return jax.device_count()


# ---------------------------------------------------------------------------
# RNG (ref: Generator per place + paddle.seed). A single root key plus a
# fold-in counter gives deterministic, splittable eager randomness; traced
# code must use rng_scope (see nn/layer.py) so keys are explicit jit inputs.
# ---------------------------------------------------------------------------
class Generator:
    def __init__(self, seed: int = 0):
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        # LAZY key creation: PRNGKey allocates a device array, and the
        # module-level default generator must not touch the device at
        # `import paddle_tpu` time (a wedged remote backend would hang
        # the import; also keeps array-only imports fast)
        self._key = None
        self._counter = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        return self._key

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.key, self._counter)


_default_generator = Generator(int(os.environ.get("PADDLE_TPU_SEED", "0")))


def seed(s: int):
    """ref: paddle.seed — reseeds the global generator."""
    _default_generator.manual_seed(s)
    return _default_generator


def default_generator() -> Generator:
    return _default_generator


def next_rng_key():
    """Next eager PRNG key. Inside a traced rng_scope, pulls from the scope
    instead so the key is a proper jit input (see nn/layer.py)."""
    scope = getattr(_state, "rng_scope", None)
    if scope is not None:
        return scope.next_key()
    return _default_generator.next_key()


@contextlib.contextmanager
def _rng_scope_ctx(scope):
    prev = getattr(_state, "rng_scope", None)
    _state.rng_scope = scope
    try:
        yield scope
    finally:
        _state.rng_scope = prev


class RNGScope:
    """Deterministic key stream derived from one root key by fold-in."""

    def __init__(self, key):
        self._key = key
        self._counter = 0

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self._key, self._counter)

    def scope(self):
        return _rng_scope_ctx(self)


def rng_scope(key):
    """Route all framework randomness below this context to `key`."""
    return RNGScope(key).scope()


def in_dynamic_mode() -> bool:
    """ref: paddle.in_dynamic_mode — eager unless inside a jax trace."""
    try:
        from jax.core import trace_state_clean
        return trace_state_clean()
    except Exception:
        return True


class _DtypeInfo:
    __slots__ = ("min", "max", "bits", "dtype", "eps", "tiny", "smallest_normal")

    def __repr__(self):
        return f"{type(self).__name__}(dtype={self.dtype})"


def iinfo(dtype):
    """ref: paddle.iinfo."""
    import numpy as np
    d = convert_dtype(dtype)
    inf = np.iinfo(np.dtype(str(jnp.dtype(d))))
    out = _DtypeInfo()
    out.min, out.max, out.bits = int(inf.min), int(inf.max), int(inf.bits)
    out.dtype = str(inf.dtype)
    return out


def finfo(dtype):
    """ref: paddle.finfo."""
    d = convert_dtype(dtype)
    inf = jnp.finfo(d)
    out = _DtypeInfo()
    out.min, out.max, out.bits = float(inf.min), float(inf.max), int(inf.bits)
    out.eps = float(inf.eps)
    out.tiny = float(inf.tiny)
    out.smallest_normal = float(inf.smallest_normal)
    out.dtype = str(inf.dtype)
    return out


def get_rng_state(device=None):
    """ref: paddle.get_rng_state — snapshot of the global generator."""
    g = _default_generator
    return {"seed": g.initial_seed(), "counter": g._counter}


def set_rng_state(state, device=None):
    """ref: paddle.set_rng_state."""
    g = _default_generator
    g.manual_seed(int(state["seed"]))
    g._counter = int(state.get("counter", 0))


# the reference's CUDA-specific variants map to the same global generator
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


class LazyGuard:
    """ref: paddle.LazyGuard gate — delayed parameter materialization is a
    Program-era feature for CPU-bound giant-model init. The TPU path
    constructs params as jax arrays whose initializers are already lazy
    device computations (no host round trip), and sharded construction
    belongs to `shard_model` + the Engine's placement; a distinct lazy
    mode would add staging complexity with no TPU win. Using it raises
    with that recipe."""

    def __enter__(self):
        raise NotImplementedError(
            "LazyGuard: construct the model normally (param init is "
            "already device-lazy under XLA) and use "
            "paddle_tpu.distributed.fleet.mpu.shard_model(model, mesh) "
            "for sharded placement of large models")

    def __exit__(self, *a):
        return False
