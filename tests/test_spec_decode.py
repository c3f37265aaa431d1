"""Speculative-decoding tests (DESIGN.md §10): the greedy exactness oracle
(spec output == target-only output, token for token, for ANY draft) across
registry-native draft/target pairs and both slot engines, the multi-token
verify step vs sequential decode, spec_k=1 degeneration, EOS inside the
window, rollback across paged KV block boundaries, and trace <-> metrics
reconciliation of the acceptance counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.nn.module import unbox
from repro.obs import MetricsRegistry, Tracer
from repro.serve.engine import Request, ServeEngine
from repro.serve.spec import build_draft_from_train, draft_arch

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def lm():
    arch = get_smoke("smollm-360m", compute_mode="dense", remat=False)
    tparams = unbox(build_model(arch, phase="train").init(KEY))
    return arch, tparams


def _prompts(vocab, n=4, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=int(rng.randint(3, 12))).astype(np.int32)
            for _ in range(n)]


def _drain(arch, tparams, prompts, *, max_new=10, eos_id=None, **kw):
    eng = ServeEngine.from_trained(tparams, arch, batch_size=4, max_len=64, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new, eos_id=eos_id))
    done = eng.run()
    return {r.rid: list(map(int, r.output)) for r in done}, eng


# ---------------------------------------------------------------------------
# exactness oracle: greedy spec decode == target-only decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["continuous", "paged"])
@pytest.mark.parametrize("draft", ["qnn8", "bnn", "small"])
def test_spec_parity(lm, engine, draft):
    """The ISSUE's oracle pairs: bnn→dense, qnn8→dense, small-dense→dense on
    both slot engines. Greedy spec decode must be token-for-token identical
    to target-only decode no matter how bad the draft is."""
    arch, tparams = lm
    prompts = _prompts(arch.vocab)
    base, _ = _drain(arch, tparams, prompts, engine=engine)
    spec, eng = _drain(arch, tparams, prompts, engine=engine,
                       spec_draft=draft, spec_k=4)
    assert spec == base
    assert eng.metrics.spec_rounds > 0
    assert eng.metrics.spec_drafted_tokens >= eng.metrics.spec_accepted_tokens


def test_spec_weight_tied_draft_accepts(lm):
    """A dense draft of a dense target is the target itself: every rejection
    can only come from budget truncation of the final round, so the accept
    rate is bounded below by (tokens - k)/tokens per request."""
    arch, tparams = lm
    prompts = _prompts(arch.vocab)
    _, eng = _drain(arch, tparams, prompts, engine="continuous",
                    spec_draft="dense", spec_k=4)
    assert eng.metrics.spec_accept_rate > 0.5
    assert eng.metrics.spec_tokens_per_round > 2.0


# ---------------------------------------------------------------------------
# multi-token verify step == sequential decode steps
# ---------------------------------------------------------------------------


def test_decode_verify_matches_sequential_decode(lm):
    """Feed the SAME token window through C sequential decode_step calls and
    one decode_verify call from the same prefilled cache: greedy choices at
    every window position must agree — that equivalence is what makes the
    accept rule exact."""
    from repro.serve import BucketedPrefill, KVSlotManager

    arch, tparams = lm
    from repro.core.convert import tree_to_serve

    api = build_model(arch, phase="serve")
    params = tree_to_serve(tparams, arch.linear_spec())
    prompt = np.arange(5, dtype=np.int32) % arch.vocab
    c = 4

    kv_a = KVSlotManager(api, n_slots=1, max_len=64, quantized=False)
    kv_b = KVSlotManager(api, n_slots=1, max_len=64, quantized=False)
    pre = BucketedPrefill(api, max_len=64, quantized=False)
    logits, cache = pre(params, prompt)
    kv_a.write_prefill(0, cache)
    kv_b.write_prefill(0, cache)
    t0 = int(np.argmax(logits))
    window = [t0]

    # sequential reference: C decode steps, each consuming the previous
    # greedy token (exactly the token sequence the window verifies)
    seq = []
    cache_a, pos = kv_a.cache, len(prompt)
    for j in range(c):
        lg, cache_a = api.decode_step(
            params, jnp.asarray([[window[j]]]), cache_a,
            jnp.asarray([pos + j], jnp.int32))
        nt = int(np.argmax(lg[0, -1]))
        seq.append(nt)
        if j + 1 < c:
            window.append(nt)

    lg, _ = api.decode_verify(
        params, jnp.asarray([window], jnp.int32), kv_b.cache,
        jnp.asarray([len(prompt)], jnp.int32))
    assert list(np.argmax(np.asarray(lg)[0], axis=-1)) == seq


# ---------------------------------------------------------------------------
# degeneration / validation
# ---------------------------------------------------------------------------


def test_spec_k1_degenerates_to_plain_decode(lm):
    """spec_k=1 must not build any draft machinery — it IS normal decode."""
    arch, tparams = lm
    prompts = _prompts(arch.vocab)
    base, _ = _drain(arch, tparams, prompts, engine="continuous")
    out, eng = _drain(arch, tparams, prompts, engine="continuous",
                      spec_draft="qnn8", spec_k=1)
    assert out == base
    assert eng.scheduler._spec_api is None
    assert eng.metrics.spec_rounds == 0
    assert eng.metrics.spec_accept_rate == 0.0


def test_spec_rejects_static_engine(lm):
    arch, tparams = lm
    with pytest.raises(ValueError, match="spec"):
        ServeEngine.from_trained(tparams, arch, engine="static",
                                 spec_draft="qnn8", spec_k=4)


def test_spec_rejects_bad_k(lm):
    arch, tparams = lm
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine.from_trained(tparams, arch, engine="continuous",
                                 spec_draft="qnn8", spec_k=0)


def test_bika_target_rejects_matmul_draft():
    """bika trains an (m, K, N) threshold tensor — no matmul weight to hand
    a dense/bnn/qnn8 draft. The conversion must refuse, not mis-convert."""
    arch = get_smoke("smollm-360m", compute_mode="bika", remat=False)
    tparams = unbox(build_model(arch, phase="train").init(KEY))
    with pytest.raises(ValueError, match="bika"):
        build_draft_from_train(tparams, arch, "dense")


def test_draft_arch_presets(lm):
    arch, _ = lm
    assert draft_arch(arch, "qnn8").compute_mode == "qnn8"
    small = draft_arch(arch, "small")
    assert small.compute_mode == "dense"
    assert small.n_layers == max(1, arch.n_layers // 2)
    with pytest.raises(ValueError, match="preset"):
        draft_arch(arch, "nope")


# ---------------------------------------------------------------------------
# EOS inside the verify window
# ---------------------------------------------------------------------------


def test_spec_eos_mid_window(lm):
    """Pick the token the target actually emits mid-stream as eos_id: the
    spec run must stop at the same point as the target-only run even when
    the draft proposes past EOS inside a window."""
    arch, tparams = lm
    prompts = _prompts(arch.vocab)
    base, _ = _drain(arch, tparams, prompts, engine="continuous", max_new=10)
    eos = base[0][4]  # a token the model provably emits mid-request
    base_eos, _ = _drain(arch, tparams, prompts, engine="continuous",
                         max_new=10, eos_id=eos)
    spec_eos, eng = _drain(arch, tparams, prompts, engine="continuous",
                           max_new=10, eos_id=eos, spec_draft="dense", spec_k=4)
    assert spec_eos == base_eos
    assert any(len(v) < 10 for v in base_eos.values())  # EOS actually fired
    # every slot freed after the EOS finishes mid-window
    assert eng.scheduler.n_active == 0


# ---------------------------------------------------------------------------
# paged engine: rollback across block boundaries
# ---------------------------------------------------------------------------


def test_spec_paged_rollback_across_block_boundary(lm):
    """kv_block_size=2 with spec_k=4 makes every verify window straddle
    block boundaries, and a half-depth draft guarantees rejections: the
    position-only rollback must stay exact across block seams."""
    arch, tparams = lm
    prompts = _prompts(arch.vocab)
    base, _ = _drain(arch, tparams, prompts, engine="paged", kv_block_size=2,
                     max_new=12)
    spec, eng = _drain(arch, tparams, prompts, engine="paged", kv_block_size=2,
                       max_new=12, spec_draft="small", spec_k=4)
    assert spec == base
    m = eng.metrics
    assert m.spec_accepted_tokens < m.spec_drafted_tokens  # rejections happened


def test_spec_paged_parity_with_several_kv_heads():
    """The paged verify window scatters and attends lane-dense pool rows
    that hold several kv heads: spec decode stays token-for-token the
    target-only decode."""
    arch = get_smoke("smollm-360m", compute_mode="dense", remat=False).replace(
        n_heads=4, n_kv_heads=2, head_dim=16)
    tparams = unbox(build_model(arch, phase="train").init(KEY))
    prompts = _prompts(arch.vocab, seed=3)
    base, _ = _drain(arch, tparams, prompts, engine="paged", kv_block_size=4, max_new=10)
    spec, eng = _drain(arch, tparams, prompts, engine="paged", kv_block_size=4,
                       max_new=10, spec_draft="small", spec_k=3)
    assert spec == base
    assert eng.metrics.spec_rounds > 0


# ---------------------------------------------------------------------------
# observability: trace <-> metrics reconciliation
# ---------------------------------------------------------------------------


def test_spec_trace_metrics_reconcile(lm):
    """The per-round spec_round trace events carry the same counts the
    RunMetrics accumulate; the bound registry counters agree too."""
    arch, tparams = lm
    tracer = Tracer()
    registry = MetricsRegistry()
    prompts = _prompts(arch.vocab)
    _, eng = _drain(arch, tparams, prompts, engine="continuous",
                    spec_draft="qnn8", spec_k=4, tracer=tracer,
                    registry=registry)
    events = [r for r in tracer.records
              if r.kind == "event" and r.name == "spec_round"]
    assert events, "spec ticks must emit spec_round trace events"
    m = eng.metrics
    assert sum(e.args["rows"] for e in events) == m.spec_rounds
    assert sum(e.args["drafted"] for e in events) == m.spec_drafted_tokens
    assert sum(e.args["accepted"] for e in events) == m.spec_accepted_tokens
    snap = registry.snapshot()

    def total(name):
        return sum(v["value"] for v in snap[name]["values"])

    assert total("serve_spec_rounds_total") == m.spec_rounds
    assert total("serve_spec_drafted_tokens_total") == m.spec_drafted_tokens
    assert total("serve_spec_accepted_tokens_total") == m.spec_accepted_tokens
