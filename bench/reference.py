"""The plain reference, shared by every architecture, and the seam each
architecture plugs into.

A configuration file (``configs/<name>.json``) names its architecture by
its ``reference`` key; ``load`` loads ``arch/<reference>.py``. Everything
that depends on the architecture lives in that one module, and nothing
here or in the harness knows an architecture. A new architecture joins
the benchmark as new files: its configuration, its module and its entries
in ``BENCHMARK.json``. The module defines:

* ``READS``: the configuration keys it reads; ``DESCRIPTIVE``: keys it
  allows and ignores (the source's bookkeeping, so that a configuration
  can be its source's ``config.json`` with keys added); ``ASSUMED``:
  ``{key: value}`` it builds in. A file states each assumed key with that
  value; one whose value is ``None`` or ``False`` (a feature that is off)
  may be left out. ``check_config`` refuses any other key, a missing
  assumed key and a contradicted value, before a weight is drawn.
* ``dims_from_config(cfg, rehearse) -> dims``: a flat dict of hashable
  sizes with at least ``n_layers`` and ``vocab``; with ``rehearse``, tiny
  sizes that a CPU rehearsal can hold, every layer kept.
* ``program_config(name, dims)``: the program's ``ModelConfig``.
* ``shapes(dims) -> {leaf: shape}`` and ``init(key, leaf, shape)``, one
  leaf in float32: ``make_flat`` draws every leaf from the seed in one
  jitted call and casts it to bfloat16.
* ``program_layout(flat)``: those arrays as the program's parameter tree.
* ``param_count(dims)``: parameters a token passes through (the model
  FLOPs per token are twice it); ``attn_width(dims)``: query heads x head
  size summed over layers, for attention FLOPs; ``kv_plane(dims)``:
  (layers, features) of the K and V pages the program stores.
* ``logits_at(flat, dims, tokens, rows, mode, pad_to)``: float32 logits
  at positions ``rows`` of the causal forward pass over ``tokens``,
  written in ``jax.numpy`` with the helpers below; it imports nothing of
  the program and has no cache, kernel or batching. Every matrix product
  goes through ``mm``, which runs at ``precision="highest"`` so that the
  TPU does not round it to bfloat16, and in ``mode="fp8"`` rounds both
  operands to float8 e4m3 first: the control, the step below the
  bfloat16 the configurations state. The sequence is padded by
  ``padded_tokens``; causal masking makes the padding invisible to real
  positions.

Here: ``served_gaps``, ``control_gaps`` and ``widest``, written over the
module's ``logits_at``; the weights' seed; the module loader and the
configuration check.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import sys
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ARCH = pathlib.Path(__file__).resolve().parent / "arch"
BUCKET = 512
Q_CHUNK = 512
# configuration keys the benchmark itself reads, whatever the architecture
SHARED_KEYS = ("source", "notes", "reference", "max_position_embeddings",
               "engine")
# what a configuration's ``engine`` may set: how many lanes one chip holds
ENGINE_KEYS = ("lanes",)


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------

def load_file(path: pathlib.Path, key: str):
    """The module in file ``path``, loaded once under the name ``key``."""
    if key not in sys.modules:
        if not path.is_file():
            raise ValueError(f"no module {path}")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def load(name: str, path: pathlib.Path = None):
    """The architecture module of ``"reference": name``: ``arch/<name>.py``,
    or the file ``path`` (a test's architecture)."""
    return load_file(path or ARCH / f"{name}.py", f"bench_arch_{name}")


def check_config(cfg: dict, arch, where: str) -> None:
    """Refuse a configuration that says what ``arch`` does not model: a key
    it neither reads nor lists, a value it assumes otherwise, or an assumed
    key left out where leaving it out does not mean the feature is off."""
    known = set(SHARED_KEYS) | set(arch.READS) | set(arch.DESCRIPTIVE) \
        | set(arch.ASSUMED)
    for key in cfg:
        if key not in known:
            raise ValueError(f"{where}: key {key!r} is not modelled by "
                             f"architecture {cfg['reference']!r}")
    for key, want in arch.ASSUMED.items():
        if key not in cfg and want not in (None, False):
            raise ValueError(f"{where}: {key!r} is missing; architecture "
                             f"{cfg['reference']!r} assumes {want!r}")
        if key in cfg and cfg[key] != want:
            raise ValueError(f"{where}: {key!r} is {cfg[key]!r}, but "
                             f"architecture {cfg['reference']!r} assumes "
                             f"{want!r}")
    for key, v in cfg.get("engine", {}).items():
        if key not in ENGINE_KEYS:
            raise ValueError(f"{where}: engine key {key!r} is not one a "
                             f"configuration may set: {ENGINE_KEYS}")
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
            raise ValueError(f"{where}: engine {key!r} is {v!r}, not a "
                             f"positive whole number")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits are fine)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _draw(arch, key, name: str, shape) -> jax.Array:
    k = jax.random.fold_in(key, sum(map(ord, name)) * 7919 + len(name))
    return arch.init(k, name, shape).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _maker(arch, dims_items: tuple):
    shp = arch.shapes(dict(dims_items))
    return jax.jit(lambda key: {n: _draw(arch, key, n, s)
                                for n, s in shp.items()})


def make_flat(arch, dims: dict, seed: int) -> dict:
    """Every parameter of ``arch`` in bfloat16, keyed by leaf name, from
    one jitted call on the device."""
    return _maker(arch, tuple(sorted(dims.items())))(seed_key(seed))


# ---------------------------------------------------------------------------
# helpers of the forward passes
# ---------------------------------------------------------------------------

def padded_tokens(tokens: np.ndarray, pad_to: int = 0) -> np.ndarray:
    """``tokens`` padded with zeros to ``pad_to`` (at least its own length)
    rounded up to ``BUCKET``, so that one shape serves a whole cell."""
    t = len(tokens)
    tok = np.zeros(-(-max(t, pad_to) // BUCKET) * BUCKET, np.int32)
    tok[:t] = tokens
    return tok


def _round(x, mode: str):
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(a, b, mode: str, spec: str = None):
    """A matrix product at ``precision="highest"``, of operands rounded to
    float8 e4m3 first where ``mode`` is ``"fp8"``."""
    a, b = _round(a, mode), _round(b, mode)
    if spec is None:
        return jnp.matmul(a, b, precision="highest")
    return jnp.einsum(spec, a, b, precision="highest")


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, D) with positions 0..S-1; rotate-half pairing."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------

def _forced(prompt, served):
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)])
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
    return seq, rows


def served_gaps(arch, flat: dict, dims: dict, prompt: np.ndarray,
                served: Sequence[int], mode: str = "fp32",
                pad_to: int = 0) -> np.ndarray:
    """Per served token, how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best).

    ``prompt`` is the context and question, ``served`` the answer tokens
    the program produced after it; the reference is teacher-forced on
    both."""
    seq, rows = _forced(prompt, served)
    lg = arch.logits_at(flat, dims, seq, rows, mode, pad_to)
    return lg.max(axis=-1) - lg[np.arange(len(served)), np.asarray(served)]


def control_gaps(arch, flat: dict, dims: dict, prompt: np.ndarray,
                 served: Sequence[int], pad_to: int = 0) -> np.ndarray:
    """The fp8 control at the same prompts and tokens: per position, how
    far the reference's logit of the token fp8 puts first lies below the
    reference's best."""
    seq, rows = _forced(prompt, served)
    ref = arch.logits_at(flat, dims, seq, rows, "fp32", pad_to)
    low = arch.logits_at(flat, dims, seq, rows, "fp8", pad_to)
    top = low.argmax(axis=-1)
    return ref.max(axis=-1) - ref[np.arange(len(served)), top]


def widest(gaps: List[np.ndarray]) -> float:
    return float(max(float(np.max(g)) for g in gaps)) if gaps else float("nan")
