"""Public kernel entry points used by the models.

Dispatch policy (``impl`` argument or ``REPRO_KERNEL_IMPL`` env):
  * unset (the default) — ``attention`` serves a call with the fused
    splash kernel (``jax.experimental.pallas.ops.tpu.splash_attention``:
    forward, dq and dk/dv kernels, grouped KV heads, fully masked causal
    blocks skipped) when everything the call shows allows it: the TPU
    backend, self-attention, no ``kv_mask``, ``q_offset`` 0, no sliding
    window, ``Sq == Sk`` a multiple of 128, query/key heads of whole or
    one and a half lane rows (128, 192 for latent attention, 256, ...),
    value heads of whole lane rows, and no multi-device model mesh. Every
    other call, and ``ssd``, takes ``blocked``. ``observe_attention``
    reports each choice. ``grouped_matmul`` takes JAX's megablox kernel
    (``gmm``, with its backward) under the same backend and mesh rules
    where every dimension is whole lane rows, else ``lax.ragged_dot``.
  * ``blocked`` — pure-jnp online-softmax / chunked-scan refs.
    Numerically identical to the Pallas kernels, lowers on any backend and
    under any SPMD sharding; this is what the dry-run and CPU training use.
  * ``splash``  — the fused splash kernel for every ``attention`` call, in
    interpret mode on the CPU backend; a call it cannot serve raises.
  * ``pallas``  — the Pallas TPU kernels, in interpret mode on the CPU
    backend only. A call the kernel cannot serve (a ``kv_mask``, a
    ``q_offset``, an ``initial_state`` or a ragged chunk) raises rather
    than quietly running the reference.
  * ``naive``   — O(S^2) einsum oracle (tests only).

Models keep the (B, S, H, D) layout; this module adapts to kernel layouts.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Iterator, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as _splash
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd
from repro.models import shardings as _sh

# the splash kernel's tiles: sequence and head_dim in multiples of a lane row
_LANES = 128

_observers: List[Callable[[str, str], None]] = []


def _impl(override: Optional[str]) -> Optional[str]:
    return override or os.environ.get("REPRO_KERNEL_IMPL")


def _backend() -> str:
    return jax.default_backend()


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled kernels elsewhere."""
    return _backend() == "cpu"


def _unservable(kernel: str, why: str) -> NotImplementedError:
    return NotImplementedError(
        f"impl='pallas': the {kernel} kernel does not support {why}; "
        f"ask for impl='blocked'")


@contextlib.contextmanager
def observe_attention(fn: Callable[[str, str], None]) -> Iterator[None]:
    """Call ``fn(path, reason)`` each time ``attention`` is traced inside
    the block with the default dispatch or ``impl='blocked'|'splash'``:
    path ``fused`` (reason ``""``) or ``blocked``, with the first rule of
    the dispatch policy the call fails (``cross``, ``kv_mask``,
    ``q_offset``, ``sliding_window``, ``lengths``, ``seq``, ``head_dim``,
    ``mesh``, ``backend``) or ``impl`` where it was named."""
    _observers.append(fn)
    try:
        yield
    finally:
        _observers.remove(fn)


def _served(path: str, reason: str = "") -> None:
    for fn in list(_observers):
        fn(path, reason)


def _heads_untiled(d_qk: int, d_v: int) -> bool:
    """Whether the splash tiles cannot hold these heads: query/key heads
    of whole or half lane rows and at least one (latent attention's 192
    passes, 64 does not), value heads of whole lane rows."""
    return d_qk < _LANES or d_qk % (_LANES // 2) != 0 or d_v % _LANES != 0


def _fused_refusal(q, k, v, *, cross: bool = False, sliding_window: int = 0,
                  q_offset=0, kv_mask=None) -> Optional[str]:
    """The first rule of the dispatch policy that keeps this call off the
    fused kernel, or None where the kernel serves it."""
    mesh = _sh.get_mesh()
    sq, sk = q.shape[1], k.shape[1]
    rules = (
        ("cross", cross),
        ("kv_mask", kv_mask is not None),
        ("q_offset", not (isinstance(q_offset, int) and q_offset == 0)),
        ("sliding_window", bool(sliding_window)),
        ("lengths", sq != sk),
        ("seq", sq % _LANES != 0),
        ("head_dim", _heads_untiled(q.shape[-1], v.shape[-1])),
        ("mesh", mesh is not None and mesh.size > 1),
        ("backend", _backend() != "tpu"),
    )
    return next((why for why, fails in rules if fails), None)


def _block(seq: int) -> int:
    """Splash tile for a sequence: the whole of one up to 256, else the
    largest of 512, 256, 128 dividing it into at least two blocks, so a
    causal mask has fully masked blocks to skip."""
    if seq <= 2 * _LANES:
        return seq
    return next(b for b in (512, 256, _LANES) if seq % b == 0 and seq > b)


def _splash_kernel(seq: int, heads: int, causal: bool, interpret: bool):
    """The cached splash kernel for a static shape, built again where its
    mask arrays were deleted (a process that frees every live array
    between two trainers, as the benchmark's calibration does)."""
    kernel = _built_splash_kernel(seq, heads, causal, interpret)
    if any(a.is_deleted() for a in jax.tree.leaves(kernel)):
        _built_splash_kernel.cache_clear()
        kernel = _built_splash_kernel(seq, heads, causal, interpret)
    return kernel


@functools.lru_cache(maxsize=16)
def _built_splash_kernel(seq: int, heads: int, causal: bool,
                         interpret: bool):
    """One splash kernel and its mask per static shape (built eagerly,
    also when first asked for inside a trace)."""
    b = _block(seq)
    # with one KV block, dq comes out of the dk/dv kernel rounded once, as
    # from the dq kernel; with more, that kernel would round a partial dq
    # per KV block before their sum, so dq keeps its own kernel
    one_kv_block = b == seq
    dq_blocks = {} if one_kv_block else dict(block_q_dq=b, block_kv_dq=b)
    sizes = _splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=one_kv_block, **dq_blocks)
    one = (_splash.CausalMask((seq, seq)) if causal
           else _splash.FullMask((seq, seq)))
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(
            _splash.MultiHeadMask([one] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def _splash_attention(q, k, v, *, causal: bool) -> jnp.ndarray:
    """(B, S, H, D) self-attention through the splash kernel, vmapped over
    the batch; the 1/sqrt(D) scale (D of q and k) is folded into q in f32
    and rounded once, as the matmul unit rounds the blocked path's f32 q.
    The output has v's head width."""
    b, s, hq, d = q.shape
    kernel = _splash_kernel(s, hq, causal, _interpret())
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads_first = (0, 2, 1, 3)
    out = jax.vmap(kernel)(qs.transpose(heads_first),
                           k.transpose(heads_first),
                           v.transpose(heads_first))
    return out.transpose(heads_first)


def attention(
    q: jnp.ndarray,               # (B, Sq, Hq, D)
    k: jnp.ndarray,               # (B, Sk, Hkv, D)
    v: jnp.ndarray,               # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_offset: int = 0,
    kv_mask: Optional[jnp.ndarray] = None,
    cross: bool = False,
    impl: Optional[str] = None,
    block_q: int = 128,
    block_k: int = 512,
) -> jnp.ndarray:
    """Multi-head (GQA) attention with causal / sliding-window masking.
    ``cross`` marks keys and values that come from another sequence."""
    impl = _impl(impl)
    if impl == "pallas":
        if kv_mask is not None or q_offset != 0:
            raise _unservable("flash_attention", "kv_mask or q_offset")
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = _fa.flash_attention(
            qt, kt, vt, causal=causal, sliding_window=sliding_window,
            block_q=block_q, block_k=block_k, interpret=_interpret())
        return out.transpose(0, 2, 1, 3)
    if impl == "naive":
        return _ref.attention_naive(
            q, k, v, causal=causal, sliding_window=sliding_window,
            q_offset=q_offset, kv_mask=kv_mask)
    why = _fused_refusal(q, k, v, cross=cross,
                         sliding_window=sliding_window, q_offset=q_offset,
                         kv_mask=kv_mask)
    if impl == "splash" and why not in (None, "backend"):
        raise NotImplementedError(
            f"impl='splash': the splash kernel does not serve this call "
            f"({why}); ask for impl='blocked'")
    if impl == "splash" or (impl is None and why is None):
        _served("fused")
        return _splash_attention(q, k, v, causal=causal)
    _served("blocked", why if impl is None else "impl")
    return _ref.attention_blocked(
        q, k, v, causal=causal, sliding_window=sliding_window,
        q_offset=q_offset, kv_mask=kv_mask, block_k=block_k)


# megablox's row tile for an operand of more rows than it
GMM_ROW_TILE = 512


def _gmm_tile(dim: int, largest: int) -> int:
    """A megablox tile along one dimension: the whole of a dimension up to
    ``largest`` (1408, an expert's width, is 11 lane rows: its only tiles
    are 128 and itself), else the largest of 512, 256, 128 dividing it."""
    if dim <= largest:
        return dim
    return next(t for t in (512, 256, _LANES) if dim % t == 0)


def _gmm_tiles(m: int, k: int, n: int):
    """megablox's tiling for one product (forward, or either product of
    its backward), looked up per shape."""
    return (_gmm_tile(m, GMM_ROW_TILE), _gmm_tile(k, 1536),
            _gmm_tile(n, 1536))


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, *,
                   impl: Optional[str] = None) -> jnp.ndarray:
    """Rows ``[o_g, o_g + group_sizes[g])`` of ``lhs`` (M, K) times
    ``rhs[g]`` (G, K, N), with ``o_g`` the sizes before ``g``; rows past
    the last group come out zero. On the fused path the products visit
    only the tiles the groups hold, but megablox then zero-fills the
    rows past them in a pass over all of M x N, so M costs memory
    traffic whatever the groups hold. Outputs ``lhs``'s dtype,
    accumulated in f32.

    The fused path is megablox's ``gmm`` and its backward (``tgmm`` for
    the weights), told of the rows past the groups as one more group that
    no weight holds; ``impl='pallas'`` runs it in interpret mode on the
    CPU backend, ``impl='blocked'`` takes ``lax.ragged_dot``."""
    impl = _impl(impl)
    m, k = lhs.shape
    n = rhs.shape[-1]
    mesh = _sh.get_mesh()
    tiled = all(d % _LANES == 0 for d in (m, k, n))
    fused = impl == "pallas" or (
        impl is None and tiled and _backend() == "tpu"
        and (mesh is None or mesh.size == 1))
    if not fused:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(lhs.dtype)
    rest = m - jnp.sum(group_sizes)
    sizes = jnp.concatenate([group_sizes, rest[None]]).astype(jnp.int32)
    return _megablox.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiles,
                         jnp.zeros((), jnp.int32), None, False, _interpret())


def decode_attention(
    q: jnp.ndarray,               # (B, 1, Hq, D)
    k_cache: jnp.ndarray,         # (B, Sk, Hkv, D)
    v_cache: jnp.ndarray,
    *,
    q_offset,                     # scalar/traced absolute position
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token attention over a (possibly ring-buffered) KV cache.

    Pure einsum: with one query the op is memory-bound and XLA's sharded
    softmax (partial max/sum + all-reduce over a sequence-sharded cache)
    is already optimal — no kernel needed.
    """
    b, sk, hkv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    q32 = q.astype(jnp.float32).reshape(b, hkv, g, d)
    k32 = k_cache.astype(jnp.float32)
    v32 = v_cache.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bhgd,bkhd->bhgk", q32, k32) * scale
    k_pos = jnp.arange(sk)
    allow = k_pos[None, :] <= jnp.asarray(q_offset).reshape(-1, 1)
    if kv_mask is not None:
        allow = allow & kv_mask.astype(bool)
    s = jnp.where(allow[:, None, None, :], s, _ref.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def ssd(
    x: jnp.ndarray,       # (B, S, H, P)
    dt: jnp.ndarray,      # (B, S, H)
    A: jnp.ndarray,       # (H,)
    B_mat: jnp.ndarray,   # (B, S, N)
    C_mat: jnp.ndarray,   # (B, S, N)
    D: jnp.ndarray,       # (H,)
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
    impl: Optional[str] = None,
):
    """Mamba2 SSD over a sequence; returns (y, final_state)."""
    impl = _impl(impl) or "blocked"
    s = x.shape[1]
    chunk = min(chunk, s)
    if impl == "pallas":
        if initial_state is not None or s % chunk:
            raise _unservable("ssd_scan", "initial_state or a sequence "
                              "that is not a multiple of the chunk")
        xt = x.transpose(0, 2, 1, 3)
        dtt = dt.transpose(0, 2, 1)
        y = _ssd.ssd_scan(xt, dtt, A, B_mat, C_mat, D,
                          chunk=chunk, interpret=_interpret())
        return y.transpose(0, 2, 1, 3), None
    if impl == "naive":
        return _ref.ssd_naive(x, dt, A, B_mat, C_mat, D,
                              initial_state=initial_state)
    if s % chunk:
        pad = chunk - s % chunk
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))
        y, st = _ref.ssd_chunked(xp, dtp, A, Bp, Cp, D, chunk=chunk,
                                 initial_state=initial_state)
        return y[:, :s], st
    return _ref.ssd_chunked(x, dt, A, B_mat, C_mat, D, chunk=chunk,
                            initial_state=initial_state)


ssd_decode_step = _ref.ssd_decode_step
