"""Lane writes from fetched pages in their stored form: the lane rows a
run lands in are bitwise those of the host path (decompress on the host,
``join_kv``, cast to the cache dtype on the host), rows past the run keep
what they held, and the donated writer compiles once per page window."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.compression import (
    DropQuantCompression, KIVICompression, NoCompression,
    StreamingLLMCompression,
)
from repro.core.compression.base import StoredKV
from repro.models import build_model
from repro.serving import scheduler
from repro.serving.baselines import build_engine
from repro.serving.chunking import join_kv, split_kv, tail_kv
from repro.serving.runner import (
    ModelRunner, _layer_cache_refs, cache_to_kvdata, kvdata_to_cache,
)
from repro.serving.scheduler import ContinuousBatcher
from repro.serving.timemodel import A100, TimeModel
from repro.serving.workload import (
    Request, make_prefix_sharing_contexts, round_robin_requests,
)

PAGE = 16
FULL = "adaptcache-8b"
N_ACTIVE = 8_030_000_000


def _bf16(cfg):
    """The smoke configurations run float32; served lanes hold bf16."""
    return dataclasses.replace(cfg, dtype="bfloat16",
                               param_dtype="bfloat16")


def _gqa():
    return _bf16(dataclasses.replace(get_config("qwen3-1.7b", smoke=True),
                                     n_layers=3))


def _mha64():
    cfg = get_config("minicpm-2b", smoke=True)
    return _bf16(dataclasses.replace(cfg, n_layers=3, head_dim=64,
                                     d_model=cfg.n_heads * 64))


CONFIGS = {"gqa": _gqa, "mha64": _mha64,
           "mla": lambda: _bf16(get_config("deepseek-v2-lite-16b",
                                           smoke=True)),
           "hybrid": lambda: _bf16(get_config("jamba-1.5-large-398b",
                                              smoke=True))}


def _batcher(cfg, capacity, n_slots=2, page_tokens=PAGE):
    tm = TimeModel(get_config(FULL), A100, N_ACTIVE)
    return ContinuousBatcher(build_model(cfg), {}, tm, n_slots=n_slots,
                             capacity=capacity, page_tokens=page_tokens)


def _kv(cfg, t, seed):
    """A random host entry of ``t`` tokens laid out as the runner stores
    one (``cache_to_kvdata``), from a batch-1 cache of random values."""
    model = build_model(cfg)
    rng = np.random.RandomState(seed)
    cache = jax.tree.map(
        lambda x: rng.standard_normal(x.shape).astype(np.float32),
        model.init_cache(batch=1, capacity=t))
    return cache_to_kvdata(cache, cfg, t)


def _host_lane(b, cfg, pieces):
    """The host path's lane: each piece decompressed on the host, joined,
    cast to the cache dtype on the host into a batch-1 cache."""
    kv = join_kv([p.kv if isinstance(p, StoredKV) else p for p in pieces])
    cache, n = kvdata_to_cache(kv, cfg, b.model, b.capacity)
    return cache, n


def _lane(cache, cfg, lane):
    """Per layer, per leaf: the lane's rows (or state) as host arrays."""
    out = []
    for _, _, (sect, j, g) in _layer_cache_refs(cache, cfg):
        for sub, leaves in cache[sect][j].items():
            for name, leaf in leaves.items():
                a = np.asarray(leaf[g] if g is not None else leaf)
                out.append(((sect, j, g, sub, name), a[lane]))
    return out


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


METHODS = {
    "kivi8": lambda p: (KIVICompression().compress(p, 0.0, bits=8),
                        KIVICompression()),
    "kivi4": lambda p: (KIVICompression().compress(p, 0.0, bits=4),
                        KIVICompression()),
    "kivi2": lambda p: (KIVICompression().compress(p, 0.0, bits=2),
                        KIVICompression()),
    # keep half at 4 bits; keep a quarter (5 rows) at 2 bits, whose K
    # codes are padded to the group
    "drop_kivi": lambda p: (DropQuantCompression().compress(
        p, DropQuantCompression().rates(p)[1]), DropQuantCompression()),
    "drop_kivi_pad": lambda p: (DropQuantCompression().compress(
        p, DropQuantCompression().rates(p)[5]), DropQuantCompression()),
    "streaming_llm": lambda p: (StreamingLLMCompression().compress(p, 0.5),
                                StreamingLLMCompression()),
    "none": lambda p: (NoCompression().compress(p, 1.0), NoCompression()),
}


def _stored(page, method):
    entry, codec = METHODS[method](page)
    return StoredKV(entry, codec)


def _check(b, cfg, lane, pieces, before):
    """Admit ``pieces`` into ``lane``: rows [0, n) are the host path's bit
    for bit, every other row and lane is as it was in ``before``."""
    want, n = _host_lane(b, cfg, pieces)
    req = Request(1, "c", np.array([3, 4]), 0.0, "qa", 2)
    b.admit(lane, req, pieces, n, 0.0)
    assert b.slots[lane].write_slot == n
    got = _lane(b.cache, cfg, lane)
    ref = dict(_lane(want, cfg, 0))
    old = dict(_lane(before, cfg, lane))
    for key, a in got:
        sub = key[3]
        if sub == "mamba":                       # whole-lane state
            assert _bits(a).tobytes() == _bits(ref[key]).tobytes(), key
            continue
        assert _bits(a[:n]).tobytes() == _bits(ref[key][:n]).tobytes(), key
        assert _bits(a[n:]).tobytes() == _bits(old[key][n:]).tobytes(), key
    other = 1 - lane
    for (key, a), (_, o) in zip(_lane(b.cache, cfg, other),
                                _lane(before, cfg, other)):
        assert _bits(a).tobytes() == _bits(o).tobytes(), key


def _filled(cfg, capacity, seed=0):
    """A batcher whose lanes hold a full-capacity run of random rows, so
    that rows a write must not touch are not zeros."""
    b = _batcher(cfg, capacity)
    kv = _kv(cfg, capacity, seed)
    req = Request(0, "c", np.array([1]), 0.0, "qa", 1)
    for lane in range(b.n_slots):
        b.admit(lane, req, kv, capacity, 0.0)
    return b, jax.tree.map(np.asarray, b.cache)


@pytest.mark.parametrize("cfg_name", ["gqa", "mha64"])
@pytest.mark.parametrize("method", list(METHODS))
def test_fetched_pages_land_bitwise_as_the_host_path(cfg_name, method):
    cfg = CONFIGS[cfg_name]()
    b, before = _filled(cfg, 8 * PAGE, seed=1)
    pages, _ = split_kv(_kv(cfg, 3 * PAGE + 5, 2), PAGE)
    run = [_stored(p, method) for p in pages]
    # and a prefilled tail after the pages, cast on the host
    run.append(tail_kv(_kv(cfg, 3 * PAGE + 5, 3), 3 * PAGE))
    _check(b, cfg, 1, run, before)


@pytest.mark.parametrize("cfg_name", ["gqa", "mha64"])
def test_a_run_that_ends_at_the_lane_capacity(cfg_name):
    """Pieces of every method; the last window would pass the lane's end,
    so the writer starts it earlier and shifts its rows."""
    cfg = CONFIGS[cfg_name]()
    methods = sorted(METHODS, key=lambda m: m == "drop_kivi_pad")
    pages, _ = split_kv(_kv(cfg, len(methods) * PAGE, 4), PAGE)
    run = [_stored(p, m) for p, m in zip(pages, methods)]
    n = sum(p.n_tokens for p in run)
    assert n % PAGE and run[-1].n_tokens < PAGE
    b, before = _filled(cfg, n, seed=5)
    _check(b, cfg, 0, run, before)
    with pytest.raises(ValueError, match="overflows"):
        b.admit(0, Request(2, "c", np.array([1]), 0.0, "qa", 1),
                run + [run[0]], n + PAGE, 0.0)


@pytest.mark.parametrize("cfg_name", ["mla", "hybrid"])
def test_host_pieces_reach_every_layer_kind(cfg_name):
    """MLA latents and whole-lane Mamba state go through the same
    writer, window by window, as the host path lays them out."""
    cfg = CONFIGS[cfg_name]()
    b, before = _filled(cfg, 6 * PAGE, seed=6)
    kv = _kv(cfg, 2 * PAGE + 7, 7)
    _check(b, cfg, 1, [kv], before)
    if cfg_name == "mla":                        # stored MLA pages too
        before = jax.tree.map(np.asarray, b.cache)
        pages, _ = split_kv(kv, PAGE)
        _check(b, cfg, 0, [_stored(p, "kivi4") for p in pages]
               + [_stored(pages[0], "streaming_llm")], before)


def test_writer_donates_and_compiles_once_per_page_window():
    """Runs of 1, 4 and 7 fetched pages, and a host entry of three full
    windows, trace no new program once the batcher warmed its two (a
    float32 window of a fetched page, a cache-dtype window of a host
    piece), and the cache buffers each write was given are deleted."""
    cfg = _gqa()
    n0 = scheduler._write_window._cache_size()
    # a lane shape no other test builds, so the count starts here
    b = _batcher(cfg, 7 * PAGE, n_slots=3)
    assert scheduler._write_window._cache_size() == n0 + 2
    pages, _ = split_kv(_kv(cfg, 7 * PAGE, 8), PAGE)
    req = Request(1, "c", np.array([1]), 0.0, "qa", 1)
    for n_pages in (1, 4, 7):
        given = jax.tree.leaves(b.cache)
        run = [_stored(p, "kivi4") for p in pages[:n_pages]]
        b.admit(n_pages % 3, req, run, n_pages * PAGE, 0.0)
        assert b.slots[n_pages % 3].write_slot == n_pages * PAGE
        assert all(leaf.is_deleted() for leaf in given)
    b.admit(0, req, _kv(cfg, 3 * PAGE, 9), 3 * PAGE, 0.0)
    assert scheduler._write_window._cache_size() == n0 + 2


@pytest.fixture(scope="module")
def runner():
    cfg = _bf16(get_config(FULL, smoke=True))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return ModelRunner(model, params, capacity=256)


def _served(runner, tmp, policy, remainder):
    rng = np.random.RandomState(21)
    contexts = make_prefix_sharing_contexts(
        rng, runner.model.cfg.vocab_size, n_docs=2, n_variants=2,
        prefix_len=128, suffix_len=72, n_probes=2)
    reqs = round_robin_requests(contexts, 10, 0.05, max_new_tokens=4)
    rig = build_engine(runner, contexts, get_config(FULL), N_ACTIVE,
                       policy=policy, dram_entries=40.0, ssd_entries=100.0,
                       n_lanes=2, ssd_root=str(tmp), page_tokens=64,
                       chunk_tokens=32, remainder_cache=remainder)
    res = rig.engine.process(reqs, skip_quality=True)
    return [dataclasses.astuple(r) for r in res]


@pytest.mark.parametrize("policy,remainder", [(("kivi", 0.16), False),
                                              (("streaming_llm", 0.5), True)])
def test_engine_serves_what_the_host_path_serves(runner, tmp_path,
                                                 monkeypatch, policy,
                                                 remainder):
    """The same requests through the engine, once as it is and once with
    every run decompressed and joined on the host before its admission:
    the same tokens, times and hits. Page hits, remainders and prefilled
    tails all occur."""
    served = _served(runner, tmp_path / "device", policy, remainder)
    admit = ContinuousBatcher.admit
    kinds = set()

    def host_admit(self, lane, req, kv, *a, **k):
        if not isinstance(kv, dict):
            kinds.update(type(p).__name__ for p in kv)
            kv = join_kv([p.kv if isinstance(p, StoredKV) else p
                          for p in kv])
        return admit(self, lane, req, kv, *a, **k)
    monkeypatch.setattr(ContinuousBatcher, "admit", host_admit)
    host = _served(runner, tmp_path / "host", policy, remainder)
    assert kinds == {"StoredKV", "dict"}
    assert served == host
