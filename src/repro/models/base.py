"""Model-building utilities: unified architecture config, scan-over-layers
with boxed params (compile time independent of depth), and the ModelAPI
facade that the launcher / trainer / server consume.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attn import pool_row_lanes
from repro.nn.linear import LinearSpec
from repro.nn.module import P, unbox

__all__ = [
    "ArchConfig",
    "KVCacheLayout",
    "KV_CACHE_LOGICAL_AXES",
    "ModelAPI",
    "PAGED_KV_LOGICAL_AXES",
    "PagedKVLayout",
    "init_paged_kv",
    "kv_cache_layout",
    "stack_layers",
    "scan_blocks",
    "scan_blocks_aux",
    "scan_blocks_with_cache",
]


class KVCacheLayout(NamedTuple):
    """Layout contract between ``init_cache``/``prefill``/``decode_step`` and
    the serving runtime (serve/kv.py): every KV leaf is stacked as
    ``(n_layers, n_slots, max_len, n_kv_heads, head_dim)`` (scale leaves carry
    a trailing 1 instead of head_dim). The batch axis IS the slot axis — the
    continuous scheduler allocates rows of it to requests and frees them the
    moment a request finishes.
    """

    n_layers: int
    n_slots: int
    max_len: int
    n_kv_heads: int
    head_dim: int


# Logical sharding axes of the KV layout contract, one per rank-5 dim. Only
# ``kv_heads`` maps to a mesh axis (tensor parallelism shards attention by
# head); layers/slots/positions stay local so slot splice + per-row decode
# writes never cross devices. ``distributed.sharding.kv_cache_shardings``
# binds these names to a mesh with the standard divisibility fallback
# (a head count that does not divide the model axis replicates instead).
KV_CACHE_LOGICAL_AXES = ("layers", None, None, "kv_heads", None)


class PagedKVLayout(NamedTuple):
    """Layout contract of the *paged* KV block pool (serve/paged_kv.py).

    Every payload leaf (``k``, ``v``) is ``(n_layers, n_phys_blocks,
    block_size, row_lanes)``: the kv heads are folded into the minor (lane)
    dim, head ``h`` being lanes ``[h*head_dim, (h+1)*head_dim)``, and the row
    is padded with zero lanes to ``row_lanes``, the next multiple of 128 (a
    TPU lane tile) of ``n_kv_heads * head_dim``. An int8 pool adds
    ``k_scale``/``v_scale`` leaves ``(n_layers, n_phys_blocks, block_size,
    n_kv_heads)``, one scale per position and head. A request's logical cache
    of ``max_len`` positions is the concatenation of the ``max_len //
    block_size`` blocks named by its host-side block table; block
    ``n_phys_blocks - 1`` is the reserved parking block that inactive decode
    rows write junk into.

    Why lane-dense and padded: the TPU stores an array in the layout that
    pads it least. A block of whole rows of a multiple of 128 lanes is
    stored row-major, ``(8, 128)`` tiles over ``(block_size, row_lanes)``,
    which is how the fused kernel's BlockSpec reads it (kernels/paged_attn.py),
    so the kernel reads the pool in place and the decode and chunk scatters
    write whole rows in place. A minor pair ``(n_kv_heads, head_dim)``, or
    rows of smollm's unpadded 320 lanes, would pad; the TPU then stores the
    pool block-minor, and every program copies each layer's k and v into
    the kernel's layout and back. The padding costs nothing the row-major
    tiles would not: 320 lanes occupy 384 either way.

    Sharding (``PAGED_KV_LOGICAL_AXES``): the lane dim maps to ``kv_heads``.
    A contiguous split of unpadded rows is a split by head group when
    ``n_kv_heads`` divides the mapped mesh axes; padded rows, or heads that
    do not divide, replicate (``distributed.sharding.paged_kv_shardings``).
    Blocks and offsets stay local, so the paged gather/scatter never cross
    devices. The dense slot cache keeps ``KVCacheLayout``.
    """

    n_layers: int
    n_phys_blocks: int
    block_size: int
    n_kv_heads: int
    head_dim: int

    @property
    def kv_width(self) -> int:
        """Lanes that hold k or v: every kv head's ``head_dim`` values."""
        return self.n_kv_heads * self.head_dim

    @property
    def row_lanes(self) -> int:
        """Lanes of one stored pool row: ``kv_width`` padded to whole lane tiles."""
        return pool_row_lanes(self.n_kv_heads, self.head_dim)

    def check(self, pool) -> None:
        """Raise unless every leaf of the block-pool pytree ``pool`` has this
        layout's shape."""
        lead = (self.n_layers, self.n_phys_blocks, self.block_size)
        for name, leaf in pool.items():
            lanes = self.n_kv_heads if name.endswith("_scale") else self.row_lanes
            if tuple(leaf.shape) != lead + (lanes,):
                raise ValueError(f"pool leaf {name!r} has shape {tuple(leaf.shape)}, not "
                                 f"{lead + (lanes,)} ({self})")

# Logical sharding axes of the paged pool's rank-4 leaves (see PagedKVLayout).
PAGED_KV_LOGICAL_AXES = ("layers", None, None, "kv_heads")


def kv_cache_layout(cache) -> KVCacheLayout:
    """Read the (layers, slots, max_len, heads, hd) layout off a stacked KV
    cache pytree (the ``{"k", "v", ...}`` dict produced by ``init_cache``).
    Raises if the tree does not follow the contract above."""
    leaves = jax.tree_util.tree_leaves(cache)
    if not leaves:
        raise ValueError("empty cache pytree")
    lead = None
    for leaf in leaves:
        if leaf.ndim != 5:
            raise ValueError(
                f"KV cache leaves must be rank-5 (layers, slots, max_len, heads, hd); "
                f"got shape {leaf.shape}"
            )
        if lead is None:
            lead = leaf.shape[:4]
        elif leaf.shape[:4] != lead:
            raise ValueError(f"inconsistent cache leaves: {leaf.shape[:4]} vs {lead}")
    k = cache["k"] if isinstance(cache, dict) and "k" in cache else leaves[0]
    return KVCacheLayout(*k.shape)


def init_paged_kv(layout: PagedKVLayout, *, dtype=jnp.float32, quantized: bool = False):
    """A zeroed block pool in the ``PagedKVLayout`` contract: ``{"k", "v"}``
    in ``dtype``, or int8 payloads with f32 ``k_scale``/``v_scale``."""
    lead = (layout.n_layers, layout.n_phys_blocks, layout.block_size)
    if quantized:
        return {
            "k": jnp.zeros(lead + (layout.row_lanes,), jnp.int8),
            "v": jnp.zeros(lead + (layout.row_lanes,), jnp.int8),
            "k_scale": jnp.zeros(lead + (layout.n_kv_heads,), jnp.float32),
            "v_scale": jnp.zeros(lead + (layout.n_kv_heads,), jnp.float32),
        }
    return {"k": jnp.zeros(lead + (layout.row_lanes,), dtype),
            "v": jnp.zeros(lead + (layout.row_lanes,), dtype)}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One config describes every assigned architecture (DESIGN.md §6)."""

    name: str
    family: str  # lm | hybrid | xlstm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    activation: str = "silu"
    gated_mlp: bool = True
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None  # sliding-window attention (SWA)
    # MoE
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    # SSM / hybrid / xlstm
    ssm_state: int = 64
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    attn_every: int = 0  # zamba2: shared attention block every k ssm blocks
    slstm_every: int = 0  # xlstm: sLSTM block every k blocks (0 = all mLSTM)
    # enc-dec
    n_encoder_layers: int = 0
    encoder_seq: int = 1024  # stub frontend sequence length
    # frontend: tokens | frames (precomputed embeddings via input_specs)
    frontend: str = "tokens"
    # compute
    compute_mode: str = "dense"  # dense | bika | bnn | qnn8
    bika_m: int = 1
    bika_impl: str = "cvjp"  # fused | cvjp (bounded-mem bwd) | pallas (TPU kernel)
    pack_signs: bool = False
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    block_q: int = 256
    remat: bool = True
    tp_pad_heads: bool = False  # pad attention heads to the TP axis (§Perf)
    # paged serving attention: "fused" block-table Pallas kernel (default) |
    # "gather" XLA paged_gather oracle (bit-parity vs dense decode)
    paged_attn_route: str = "fused"
    # capability flags
    full_attention: bool = True  # True -> long_500k skipped (quadratic)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    def linear_spec(self, **over) -> LinearSpec:
        return LinearSpec(
            mode=self.compute_mode,
            m=self.bika_m,
            impl=self.bika_impl,
            pack_signs=self.pack_signs,
            param_dtype=self.param_dtype,
            compute_dtype=self.compute_dtype,
            **over,
        )

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


class ModelAPI(NamedTuple):
    """What the launcher consumes. All callables are functional/jit-able."""

    init: Callable[[jax.Array], Any]  # key -> boxed params
    apply: Callable[[Any, Any], jax.Array]  # (params, batch) -> logits
    init_cache: Callable[..., Any]  # (batch, max_len, **kw) -> cache
    decode_step: Callable[[Any, Any, Any, jax.Array], Any]  # -> (logits, cache)
    prefill: Optional[Callable[..., Any]] = None  # (params, batch, max_len) -> cache
    apply_aux: Optional[Callable[[Any, Any], Any]] = None  # -> (logits, aux_loss)
    # paged serving (families with attention KV only — see PagedKVLayout):
    # (n_phys_blocks, block_size) -> the pool's PagedKVLayout
    paged_kv_layout: Optional[Callable[[int, int], PagedKVLayout]] = None
    # (n_phys_blocks, block_size, *, quantized, dtype) -> zeroed block pool
    init_paged_cache: Optional[Callable[..., Any]] = None
    # (params, tok (S,1), pool, positions (S,), tables (S,T)) -> (logits, pool)
    decode_paged: Optional[Callable[..., Any]] = None
    # (params, chunk (1,C), pool, table (1,T), start (1,), last_in_chunk (1,))
    # -> (last-token logits (1,1,V), pool)
    prefill_chunk: Optional[Callable[..., Any]] = None
    # speculative decoding (DESIGN.md §10): multi-token verify — score a
    # (B, C) candidate window at per-row positions in ONE step, returning
    # the FULL (B, C, V) logits (one greedy token per window slot).
    # (params, window (S,C), cache, positions (S,)) -> (logits, cache)
    decode_verify: Optional[Callable[..., Any]] = None
    # paged variant: + the (S,T) block tables operand
    decode_verify_paged: Optional[Callable[..., Any]] = None


def stack_layers(key: jax.Array, n: int, init_one: Callable[[jax.Array], Any], axis_name=None):
    """Initialize n layers and stack their params on a leading 'layers' axis.

    Returns a boxed tree whose leaves are P((n, ...), (axis_name,) + axes).
    Works under jax.eval_shape (abstract init for the dry-run).
    """
    keys = jax.random.split(key, n)
    vals = jax.vmap(lambda k: unbox(init_one(k)))(keys)
    template = jax.eval_shape(init_one, keys[0])
    return jax.tree_util.tree_map(
        lambda tpl, v: P(
            v, (axis_name,) + tuple(tpl.axes if tpl.axes else (None,) * (v.ndim - 1))
        ),
        template,
        vals,
        is_leaf=lambda x: isinstance(x, P),
    )


def scan_blocks(stacked_params, x: jax.Array, body: Callable, *, remat: bool = True):
    """x -> block(params_i, x) for i in 0..L-1 via lax.scan (compact HLO)."""
    fn = jax.checkpoint(body) if remat else body

    def step(carry, p):
        return fn(p, carry), None

    y, _ = jax.lax.scan(step, x, stacked_params)
    return y


def scan_blocks_aux(stacked_params, x: jax.Array, body: Callable, *, remat: bool = True):
    """Like scan_blocks for bodies returning (x, aux_scalar); sums the aux."""
    fn = jax.checkpoint(body) if remat else body

    def step(carry, p):
        x, acc = carry
        y, aux = fn(p, x)
        return (y, acc + aux.astype(acc.dtype)), None

    (y, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), stacked_params)
    return y, aux


def scan_blocks_with_cache(stacked_params, stacked_cache, x, body, position):
    """Decode-path scan over layers threading per-layer cache.

    body(params_i, cache_i, x, position) -> (x, new_cache_i).
    Returns (x, new_stacked_cache).
    """

    def step(carry, pc):
        p, c = pc
        y, nc = body(p, c, carry, position)
        return y, nc

    y, new_cache = jax.lax.scan(step, x, (stacked_params, stacked_cache))
    return y, new_cache


def make_norm(cfg: ArchConfig):
    from repro.nn import norms

    if cfg.norm == "rmsnorm":
        return norms.rmsnorm_init, norms.rmsnorm_apply
    return (lambda d, dtype=jnp.float32: norms.layernorm_init(d, dtype)), norms.layernorm_apply
