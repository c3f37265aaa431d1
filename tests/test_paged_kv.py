"""Paged-KV serving tests: token-for-token parity of the paged engine vs the
dense continuous oracle (all four backends, 1 device and tp=2), chunked
prefill exactness, shared-prefix refcount/copy-on-write under churn, LRU
eviction, block-table exhaustion backpressure (defer, no deadlock), and the
parking-block isolation of freed decode rows."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.models.base import (
    KV_CACHE_LOGICAL_AXES,
    KVCacheLayout,
    PagedKVLayout,
    kv_cache_layout,
)
from repro.nn.module import unbox
from repro.serve import (
    ChunkedPrefill,
    PagedKVManager,
    PagedSlotScheduler,
    Request,
    ServeEngine,
    hash_prompt_blocks,
    replay_arrivals,
    serve_batch,
)

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def lm():
    cfg = get_smoke("smollm-360m", compute_mode="dense", remat=False)
    api = build_model(cfg, phase="train")
    params = unbox(api.init(KEY))
    return cfg, api, params


def _ref(api, params, prompt, n_new, max_len):
    out = serve_batch(api, params, jnp.asarray(prompt)[None],
                      max_new_tokens=n_new, max_len=max_len)
    return np.asarray(out)[0]


def _mixed_prompts(rng, vocab, n, lo=3, hi=12):
    return [rng.randint(0, vocab, size=int(rng.randint(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _drain(eng, prompts, n_new, **req_kw):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=n_new, **req_kw))
    return {r.rid: r for r in eng.run()}


# ---------------------------------------------------------------------------
# parity: paged == dense continuous == serve_batch
# ---------------------------------------------------------------------------


def test_paged_mixed_lengths_bit_identical(lm):
    cfg, api, params = lm
    rng = np.random.RandomState(0)
    prompts = _mixed_prompts(rng, cfg.vocab, 6)
    eng = ServeEngine(api, params, cfg, max_len=32, engine="paged", n_slots=3,
                      kv_block_size=8, prefill_chunk=8)
    done = _drain(eng, prompts, 6)
    assert len(done) == len(prompts)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(done[i].output, _ref(api, params, p, 6, 32))


def test_paged_vs_dense_continuous_poisson_replay(lm):
    """Same open-loop Poisson arrival trace through the dense continuous
    scheduler and the paged scheduler: identical tokens per request."""
    cfg, api, params = lm
    outs = {}
    for engine in ("continuous", "paged"):
        rng = np.random.RandomState(3)
        prompts = _mixed_prompts(rng, cfg.vocab, 8)
        arrivals = np.cumsum(rng.exponential(1e-3, len(prompts)))
        eng = ServeEngine(api, params, cfg, max_len=32, engine=engine, n_slots=2,
                          kv_block_size=8, prefill_chunk=8)
        reqs = [(float(a), Request(rid=i, prompt=p, max_new_tokens=5))
                for i, (a, p) in enumerate(zip(arrivals, prompts))]
        done, _ = replay_arrivals(eng.scheduler, reqs)
        outs[engine] = {r.rid: list(r.output) for r in done}
    assert outs["paged"] == outs["continuous"]


def test_paged_all_backends_bit_identical():
    """dense/bika/bnn/qnn8 serve-phase: paged == dense-continuous oracle,
    token for token, mixed prompt lengths."""
    for mode in ("dense", "bika", "bnn", "qnn8"):
        arch = get_smoke("smollm-360m", compute_mode=mode, remat=False)
        if mode == "bika":
            arch = arch.replace(pack_signs=True)
        api = build_model(arch, phase="serve")
        params = unbox(api.init(KEY))
        rng = np.random.RandomState(4)
        prompts = _mixed_prompts(rng, arch.vocab, 4)
        outs = {}
        for engine in ("continuous", "paged"):
            eng = ServeEngine(api, params, arch, max_len=32, engine=engine,
                              n_slots=2, kv_block_size=8, prefill_chunk=8)
            outs[engine] = {i: list(r.output)
                            for i, r in _drain(eng, prompts, 5).items()}
        assert outs["paged"] == outs["continuous"], mode


def test_paged_shared_prefix_hits_and_stays_exact(lm):
    """Requests sharing a 2-block system prompt: later admissions serve the
    prefix from cached blocks (hit tokens > 0) and outputs stay exact."""
    cfg, api, params = lm
    rng = np.random.RandomState(5)
    sys_prompt = rng.randint(0, cfg.vocab, 16).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(0, cfg.vocab, int(rng.randint(2, 6)))
                               .astype(np.int32)])
               for _ in range(5)]
    eng = ServeEngine(api, params, cfg, max_len=32, engine="paged", n_slots=2,
                      kv_block_size=8, prefill_chunk=8)
    done = _drain(eng, prompts, 5)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(done[i].output, _ref(api, params, p, 5, 32))
    m = eng.metrics
    # first request computes the prefix; the other 4 hit both blocks
    assert m.prefix_hit_tokens == 4 * 16
    assert 0 < m.prefix_hit_rate < 1
    assert m.blocks_in_use_peak > 0


def test_paged_quantized_kv_runs_and_is_deterministic(lm):
    """int8-KV on the paged engine: chunked prefill attends the DEQUANTIZED
    stored blocks (the dense whole-prompt prefill attends raw fp keys), so
    bit-parity with the dense engine is out of scope by design — but the
    path must run, drain, and be deterministic run-to-run."""
    cfg, api, params = lm
    rng = np.random.RandomState(6)
    prompts = _mixed_prompts(rng, cfg.vocab, 4)
    outs = []
    for _ in range(2):
        eng = ServeEngine(api, params, cfg, max_len=32, engine="paged", n_slots=2,
                          quantized_kv=True, kv_block_size=8, prefill_chunk=8)
        done = _drain(eng, prompts, 5)
        assert len(done) == 4 and all(len(r.output) == 5 for r in done.values())
        outs.append({i: list(r.output) for i, r in done.items()})
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_logits_exact(lm):
    """Appending a prompt through the (1, chunk) program yields the same
    last-token logits as the dense continuous engine's bucketed prefill.
    The gather route computes the same math as the bucketed prefill in a
    different XLA program, so it agrees to float rounding. The fused
    block-walk route (the default) reorders the softmax reduction online,
    so it lands ~2e-7 off; in both cases argmax absorbs it, as the
    end-to-end token-parity tests assert. (The *unpadded* whole-prompt
    prefill differs from BOTH padded paths by ~2e-7 at some lengths:
    XLA's reduction order is shape-dependent.)"""
    cfg, api, params = lm
    from repro.serve import BucketedPrefill

    gapi = build_model(cfg.replace(paged_attn_route="gather"), phase="train")
    rng = np.random.RandomState(7)
    for plen in (5, 21):
        prompt = rng.randint(0, cfg.vocab, plen).astype(np.int32)
        want, _ = BucketedPrefill(api, max_len=32, min_bucket=8)(params, prompt)

        kv = PagedKVManager(gapi, n_slots=1, max_len=32, block_size=8)
        slot = kv.alloc_slot()
        assert kv.try_admit(slot, prompt, budget=1, chunk=8) == 0
        cp = ChunkedPrefill(gapi, chunk=8, max_len=32)
        got, kv.cache, n_chunks = cp(params, kv.cache, kv.tables[slot], prompt, 0)
        assert n_chunks == -(-plen // 8)
        assert cp.misses == 1 and cp.hits == n_chunks - 1  # one program total
        # Two different XLA programs (a (1, chunk) paged step vs a (1,
        # bucket) dense prefill) may sum the same terms in a different
        # order: logits of order 1 then differ by a few f32 ulps (2.7e-7
        # seen with XLA-CPU in jax 0.9). 2e-6 is ~16 ulps at magnitude 1 —
        # reduction-order noise passes, a wrong mask or position does not.
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)
        assert int(np.argmax(got)) == int(np.argmax(want))

        kv = PagedKVManager(api, n_slots=1, max_len=32, block_size=8)
        slot = kv.alloc_slot()
        assert kv.try_admit(slot, prompt, budget=1, chunk=8) == 0
        fused, kv.cache, _ = ChunkedPrefill(api, chunk=8, max_len=32)(
            params, kv.cache, kv.tables[slot], prompt, 0)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        assert int(np.argmax(fused)) == int(np.argmax(want))


def test_chunked_prefill_single_program_across_lengths(lm):
    """Every prompt length shares the one (1, chunk) compile — the shape set
    BucketedPrefill spreads over O(log max_len) buckets collapses to 1."""
    cfg, api, params = lm
    sched = PagedSlotScheduler(api, params, cfg, n_slots=2, max_len=32,
                               block_size=8, chunk=8)
    rng = np.random.RandomState(8)
    for i, p in enumerate(_mixed_prompts(rng, cfg.vocab, 6, lo=2, hi=20)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    sched.run()
    assert sched.prefill.misses == 1
    assert sched.metrics.prefill_compiles == 1
    assert sched.metrics.prefill_chunks >= 6


# ---------------------------------------------------------------------------
# block accounting: refcount, COW, LRU, exhaustion, parking
# ---------------------------------------------------------------------------


def test_prefix_refcount_and_cow_under_churn(lm):
    cfg, api, params = lm
    kv = PagedKVManager(api, n_slots=3, max_len=32, block_size=8)
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, cfg.vocab, 17).astype(np.int32)  # 2 full blocks

    s0 = kv.alloc_slot()
    assert kv.try_admit(s0, prompt, budget=4, chunk=8) == 0
    # run the real prefill so the shared blocks hold actual (nonzero) KV —
    # the COW copy assertion below must compare real content, not the
    # pool's zero-initialized state
    cp = ChunkedPrefill(api, chunk=8, max_len=32)
    _, kv.cache, _ = cp(params, kv.cache, kv.tables[s0], prompt, 0)
    kv.register_prompt(s0, prompt)
    b0, b1 = kv._slot_blocks[s0][:2]
    assert kv.refcount(b0) == 1 and not kv.is_private(s0, 0)  # registered
    assert np.abs(np.asarray(kv.cache["k"][:, b0])).sum() > 0

    # second slot with the same prompt shares both full blocks
    s1 = kv.alloc_slot()
    assert kv.try_admit(s1, prompt, budget=4, chunk=8) == 16
    assert kv._slot_blocks[s1][:2] == [b0, b1]
    assert kv.refcount(b0) == 2 and kv.refcount(b1) == 2

    # COW: slot 1 gets its own bit-identical copy (all layers of the one
    # block); refs drop back to 1 and no other block changed
    before = np.asarray(kv.cache["k"][:, b0])
    other = np.asarray(kv.cache["k"][:, b1])
    nb = kv.ensure_private(s1, 0)
    assert nb != b0 and kv.tables[s1, 0] == nb
    assert kv.refcount(b0) == 1 and kv.refcount(nb) == 1
    assert kv.cow_copies == 1
    np.testing.assert_array_equal(np.asarray(kv.cache["k"][:, nb]), before)
    np.testing.assert_array_equal(np.asarray(kv.cache["k"][:, b0]), before)
    np.testing.assert_array_equal(np.asarray(kv.cache["k"][:, b1]), other)

    # exclusively-owned but registered block: COW just unregisters it
    assert not kv.is_private(s0, 0)
    assert kv.ensure_private(s0, 0) == b0
    assert kv.is_private(s0, 0)

    # churn: free both slots; refcounts drain, double free raises
    kv.free_slot(s0)
    assert kv.refcount(b1) == 1  # still attached to s1
    kv.free_slot(s1)
    assert kv.refcount(b1) == 0
    with pytest.raises(ValueError, match="double free"):
        kv.free_slot(s1)


def test_lru_eviction_order_and_chain_invalidation(lm):
    cfg, api, params = lm
    # pool of exactly one slot's worth of blocks: any new allocation after a
    # free must evict cached blocks, oldest first
    kv = PagedKVManager(api, n_slots=2, max_len=32, block_size=8, n_blocks=4)
    rng = np.random.RandomState(10)
    prompt_a = rng.randint(0, cfg.vocab, 17).astype(np.int32)

    s0 = kv.alloc_slot()
    kv.try_admit(s0, prompt_a, budget=4, chunk=8)
    kv.register_prompt(s0, prompt_a)
    a_blocks = list(kv._slot_blocks[s0][:2])
    kv.free_slot(s0)
    assert kv.blocks_cached == 2  # registered blocks linger, evictable
    assert kv.match_prefix(prompt_a) == a_blocks  # still a full hit

    # a disjoint prompt needing the whole pool evicts A's blocks oldest-first
    prompt_b = rng.randint(0, cfg.vocab, 25).astype(np.int32)
    s1 = kv.alloc_slot()
    assert kv.try_admit(s1, prompt_b, budget=4, chunk=8) == 0
    assert kv.evictions == 2  # both of A's cached blocks were reclaimed
    # A's chain is gone: a re-submission of A gets no cached prefix
    assert kv.match_prefix(prompt_a) == []
    kv.free_slot(s1)


def test_block_exhaustion_backpressure_no_deadlock(lm):
    """Pool sized for ONE request: the second defers (admission_deferrals
    ticks up), then admits after the first completes — everything finishes
    with exact outputs and zero stuck requests."""
    cfg, api, params = lm
    rng = np.random.RandomState(11)
    prompts = _mixed_prompts(rng, cfg.vocab, 4, lo=10, hi=20)
    eng = ServeEngine(api, params, cfg, max_len=32, engine="paged", n_slots=2,
                      kv_block_size=8, kv_n_blocks=4, prefix_cache=False,
                      prefill_chunk=8)
    done = _drain(eng, prompts, 6)
    assert len(done) == 4
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(done[i].output, _ref(api, params, p, 6, 32))
    assert eng.metrics.admission_deferrals > 0
    assert eng.scheduler.kv.blocks_free == 4  # fully drained back


def test_try_admit_defers_without_mutating(lm):
    cfg, api, params = lm
    kv = PagedKVManager(api, n_slots=2, max_len=32, block_size=8, n_blocks=4)
    rng = np.random.RandomState(12)
    s0 = kv.alloc_slot()
    assert kv.try_admit(s0, rng.randint(0, cfg.vocab, 20).astype(np.int32),
                        budget=8, chunk=8) == 0
    free_before = kv.blocks_free
    s1 = kv.alloc_slot()
    assert kv.try_admit(s1, rng.randint(0, cfg.vocab, 20).astype(np.int32),
                        budget=8, chunk=8) is None  # needs 4, has 1
    assert kv.blocks_free == free_before and not kv._slot_blocks[s1]
    kv.free_slot(s1)
    kv.free_slot(s0)


def test_parking_block_and_layout_contract(lm):
    """Freed rows point their whole table at the reserved parking block, and
    the pool pins the PagedKVLayout contract."""
    cfg, api, params = lm
    sched = PagedSlotScheduler(api, params, cfg, n_slots=2, max_len=32,
                               block_size=8, chunk=8)
    lay = sched.kv.layout
    lay.check(sched.kv.cache)
    assert lay.block_size == 8 and lay.n_kv_heads == cfg.n_kv_heads
    assert lay.n_phys_blocks == sched.kv.n_blocks + 1
    assert (sched.kv.tables == sched.kv.parking_block).all()
    rng = np.random.RandomState(13)
    sched.submit(Request(rid=0, prompt=rng.randint(0, cfg.vocab, 9)
                         .astype(np.int32), max_new_tokens=4))
    sched.run()
    assert (sched.kv.tables == sched.kv.parking_block).all()  # re-parked
    assert sched.kv.n_free_slots == 2


# ---------------------------------------------------------------------------
# the lane-dense pool layout (models/base.PagedKVLayout) and the dense one
# ---------------------------------------------------------------------------

GQA = dict(n_heads=4, n_kv_heads=2, head_dim=16)  # two kv heads per pool row


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_paged_pool_is_lane_dense(quantized):
    """init_paged_cache folds the kv heads into the minor dim: payloads
    (layers, blocks, block_size, row_lanes), kv_heads*hd lanes padded to a
    multiple of 128, int8 scales (..., kv_heads); the model's layout
    names the head split, and its check refuses other trees."""
    cfg = get_smoke("smollm-360m", remat=False).replace(**GQA)
    api = build_model(cfg, phase="serve")
    pool = api.init_paged_cache(9, 8, quantized=quantized)
    lead = (cfg.n_layers, 9, 8)
    assert pool["k"].shape == pool["v"].shape == lead + (128,)
    assert pool["k"].dtype == (jnp.int8 if quantized else jnp.float32)
    if quantized:
        assert pool["k_scale"].shape == pool["v_scale"].shape == lead + (2,)
    lay = api.paged_kv_layout(9, 8)
    lay.check(pool)
    assert lay == PagedKVLayout(cfg.n_layers, 9, 8, 2, 16)
    assert (lay.kv_width, lay.row_lanes) == (32, 128)
    assert PagedKVLayout(1, 9, 16, 5, 64).row_lanes == 384  # smollm: 320 lanes
    assert PagedKVLayout(1, 9, 16, 32, 96).row_lanes == 3072  # phi3: no padding
    with pytest.raises(ValueError):
        (lay._replace(n_kv_heads=3) if quantized else lay._replace(head_dim=128)).check(pool)
    with pytest.raises(ValueError):
        lay.check(api.init_cache(9, 8))  # the dense cache


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_dense_slot_cache_layout_unchanged(quantized):
    """The dense slot cache keeps its own contract: rank-5 (layers, slots,
    max_len, kv_heads, hd|1) leaves, heads as a dim of their own."""
    cfg = get_smoke("smollm-360m", remat=False).replace(**GQA)
    api = build_model(cfg, phase="serve")
    cache = api.init_cache(3, 16, quantized=quantized)
    assert kv_cache_layout(cache) == KVCacheLayout(cfg.n_layers, 3, 16, 2, 16)
    assert cache["k"].shape == (cfg.n_layers, 3, 16, 2, 16)
    if quantized:
        assert cache["k_scale"].shape == (cfg.n_layers, 3, 16, 2, 1)
    assert KV_CACHE_LOGICAL_AXES == ("layers", None, None, "kv_heads", None)


def test_paged_pool_shards_by_head_group():
    """The pool's lane dim splits over the model axis only when the kv heads
    divide it and the rows are unpadded (a contiguous split of the lanes is
    then a split by head group); otherwise the pool replicates rather than
    cut heads."""
    from jax.sharding import AbstractMesh, PartitionSpec

    from repro.distributed.sharding import paged_kv_shardings

    mesh = AbstractMesh((2, 2), ("data", "model"))
    split, whole = PartitionSpec(None, None, None, "model"), PartitionSpec()
    for hkv, hd, spec in ((4, 32, split), (3, 128, whole), (2, 16, whole)):
        lay = PagedKVLayout(1, 9, 8, hkv, hd)
        pool = {"k": jax.ShapeDtypeStruct((1, 9, 8, lay.row_lanes), jnp.float32),
                "k_scale": jax.ShapeDtypeStruct((1, 9, 8, hkv), jnp.float32)}
        assert {n: sh.spec for n, sh in paged_kv_shardings(mesh, pool, lay).items()} \
            == {"k": spec, "k_scale": spec}, (hkv, hd)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_cow_copies_every_head_of_a_lane_dense_block(quantized):
    """Copy-on-write in the lane-dense pool: the fresh block gets every lane
    (all kv heads) of every leaf and layer of the shared one, and nothing
    else changes."""
    cfg = get_smoke("smollm-360m", remat=False).replace(**GQA)
    api = build_model(cfg, phase="serve")
    kv = PagedKVManager(api, n_slots=2, max_len=32, block_size=8, quantized=quantized)
    rng = np.random.RandomState(14)
    kv.cache = {name: jnp.asarray(rng.randint(-100, 100, leaf.shape), leaf.dtype)
                for name, leaf in kv.cache.items()}
    prompt = rng.randint(0, cfg.vocab, 17).astype(np.int32)
    s0 = kv.alloc_slot()
    kv.try_admit(s0, prompt, budget=4, chunk=8)
    kv.register_prompt(s0, prompt)
    s1 = kv.alloc_slot()
    assert kv.try_admit(s1, prompt, budget=4, chunk=8) == 16
    b0 = kv._slot_blocks[s1][0]
    before = {name: np.asarray(leaf) for name, leaf in kv.cache.items()}
    nb = kv.ensure_private(s1, 0)
    assert nb != b0 and kv.cow_copies == 1
    for name, leaf in kv.cache.items():
        want = before[name].copy()
        want[:, nb] = before[name][:, b0]
        np.testing.assert_array_equal(np.asarray(leaf), want, err_msg=name)


def test_hash_chain_commits_to_whole_prefix():
    a = np.arange(32, dtype=np.int32)
    b = a.copy()
    b[2] = 99  # first-block divergence must change EVERY later digest
    ha, hb = hash_prompt_blocks(a, 8), hash_prompt_blocks(b, 8)
    assert len(ha) == 4
    assert all(x != y for x, y in zip(ha, hb))
    c = a.copy()
    c[20] = 99  # block-2 divergence keeps blocks 0-1, changes 2-3
    hc = hash_prompt_blocks(c, 8)
    assert hc[:2] == ha[:2] and hc[2] != ha[2] and hc[3] != ha[3]
    assert hash_prompt_blocks(a[:7], 8) == []  # no full block, no hash


def test_paged_engine_gating():
    # recurrent family: no paged model path
    cfg = get_smoke("xlstm-125m")
    api = build_model(cfg, phase="train")
    with pytest.raises(ValueError, match="paged serving"):
        PagedSlotScheduler(api, None, cfg)
    # auto never silently switches the dense-continuous default
    lm_cfg = get_smoke("smollm-360m", remat=False)
    lm_api = build_model(lm_cfg, phase="train")
    eng = ServeEngine(lm_api, unbox(lm_api.init(KEY)), lm_cfg, max_len=16)
    assert eng.engine == "continuous"
    # misaligned block size is rejected up front
    with pytest.raises(ValueError, match="multiple of block_size"):
        PagedKVManager(lm_api, n_slots=1, max_len=30, block_size=8)


def test_launcher_paged_smoke():
    from repro.launch.serve import main

    assert main(["--arch", "smollm-360m", "--smoke", "--engine", "paged",
                 "--requests", "4", "--new-tokens", "4", "--max-len", "32",
                 "--kv-block-size", "8", "--prefill-chunk", "8"]) == 0


# ---------------------------------------------------------------------------
# tp=2: paged == dense continuous on a (4, 2) mesh, all four backends
# ---------------------------------------------------------------------------


def _run_sub(body: str):
    code = ("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
""" + textwrap.dedent(body))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_paged_sharded_token_identical_all_backends_8dev():
    """Paged engine on a (4, 2) data x model mesh == dense continuous on one
    device, token for token, for dense/bika/bnn/qnn8 — KV pool leaves
    sharded kv_heads-over-model like the dense contract."""
    out = _run_sub("""
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import Request, ServeEngine

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))

    def run(mode, mesh_, engine):
        arch = get_smoke("smollm-360m", compute_mode=mode, remat=False).replace(
            n_heads=4, n_kv_heads=2, head_dim=64)
        if mode in ("bika", "bnn"):
            arch = arch.replace(pack_signs=True)
        if mode != "dense":
            arch = arch.replace(bika_impl="pallas")
        api = build_model(arch, phase="serve")
        params = unbox(api.init(jax.random.PRNGKey(0)))
        eng = ServeEngine(api, params, arch, max_len=32, engine=engine,
                          n_slots=2, kv_block_size=8, prefill_chunk=8,
                          mesh=mesh_)
        rng = np.random.RandomState(0)
        for i in range(5):
            plen = int(rng.randint(3, 12))
            eng.submit(Request(rid=i, prompt=rng.randint(0, arch.vocab, plen)
                               .astype(np.int32), max_new_tokens=6))
        return {r.rid: list(r.output) for r in eng.run()}, eng

    for mode in ("dense", "bika", "bnn", "qnn8"):
        ref, _ = run(mode, None, "continuous")
        got, eng = run(mode, mesh, "paged")
        assert ref == got, (mode, ref, got)
        sh = eng.scheduler.kv.cache["k"].sharding
        assert sh.spec == jax.sharding.PartitionSpec(None, None, None, "model"), sh
        assert eng.scheduler.prefill.misses == 1  # one chunk program, sharded too
        print(mode, "OK")
    print("PAGED_SHARDED_OK")
    """)
    assert "PAGED_SHARDED_OK" in out
