"""Real-compute P/D engines for the in-process mini-cluster.

PrefillEngine runs actual prefill batches and writes KV into a paged pool;
DecodeEngine runs continuous-batched paged decode (paged_attention kernel
for attention layers, dense recurrent states for mamba layers, dense
cross-attention KV for encoder-decoder archs). All assigned families are
supported: dense / moe / ssm / hybrid / vlm-backbone / audio (enc-dec).

Hot-loop shape discipline (the §2.2.3 perf model only holds if the
engines run as fast as the hardware allows):

  * prefill batches are padded to power-of-two length BUCKETS for EVERY
    family and run through one shared jitted forward, so the compile
    count is O(num_buckets), not O(distinct prompt lengths). Padding is
    exact by the model's pad-invariance contract (masked attention
    queries, zero-dt SSD recurrence, null-slot window-local MoE
    capacity — see models.modeling.forward_seq); suffix-only
    (prefix-reuse) prefills additionally bucket the PREFIX KV length,
    so warm admissions share one program per (prefix bucket, suffix
    bucket) pair. (The ``REPRO_PREFILL=exact`` env hatch was retired
    after the bucketed default survived three releases;
    ``bucket_prefill=False`` remains a constructor arg for
    measurement);
  * the decode iteration is ONE jitted, buffer-donated device program
    (``models.modeling.decode_step_jit``) over fixed-shape slot state —
    padded (max_slots,) token/position/mask arrays, a power-of-two
    bucketed block table, and block-stacked mamba/cross slot buffers —
    with exactly one device->host transfer per step (the argmax) and no
    per-layer pool copies (the paged pool is donated into the step).
    ``fused=False`` (constructor arg) keeps the legacy eager per-layer
    loop as the measured benchmark baseline; both paths are
    token-identical by test. (The ``REPRO_DECODE=eager`` env hatch was
    retired after the fused path survived three releases as default.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models.caches import decode_slot_state
from repro.models.config import ATTN, ModelConfig
from repro.models.modeling import (
    _attn_proj_qkv, _ffn_sublayer, _merge_heads, _split_heads,
    decode_step_jit, forward_prefill, lm_logits, mamba_sublayer_step,
    rmsnorm, rope, spec_decode_step_jit)
from repro.models.params import block_period, num_blocks
from repro.serving import trace
from repro.serving.kvcache import PagedKVPool
from repro.serving.speculative import SpecConfig

Tree = dict

# layer-streaming callback: (batch_index, attn_layer_index, k_layer
# (tokens, kv_dim), v_layer, network_depth_fraction). Invoked in network
# order as each attention layer's KV becomes available, so a transfer
# scheduler can ship layer i while layer i+1 is still prefilling
# (per-layer triggering, paper Fig. 10).
OnLayer = Callable[[int, int, jax.Array, jax.Array, float], None]

# smallest prefill length bucket; buckets double up to cfg.max_seq_len
PREFILL_BUCKET_MIN = 16

# One shared jitted prefill across every engine instance: the cache is
# keyed on (cfg, shapes), so N serving nodes of the same arch compile
# each length bucket once, not once per node. prefix_len is a TRACED
# operand (the prefix KV is padded to a static bucket), so warm
# prefix-reuse admissions retrace per (prefix bucket, suffix bucket) —
# never per distinct prefix length.
_jit_forward_prefill = jax.jit(
    forward_prefill, static_argnames=("cfg", "window", "snap_stride"))


def prefill_compile_count() -> int:
    """Live compilation-cache entries of the shared jitted prefill (the
    retrace-count guard asserts deltas on this under ragged traffic)."""
    return _jit_forward_prefill._cache_size()


def _attn_layer_order(cfg: ModelConfig) -> List[Tuple[int, int]]:
    """(blk, sub) pairs of attention layers, in network order."""
    period = block_period(cfg)
    kinds = cfg.layer_kinds()
    return [(b, s) for b in range(num_blocks(cfg)) for s in range(period)
            if kinds[s] == ATTN]


def _mamba_layer_order(cfg: ModelConfig) -> List[Tuple[int, int]]:
    period = block_period(cfg)
    kinds = cfg.layer_kinds()
    return [(b, s) for b in range(num_blocks(cfg)) for s in range(period)
            if kinds[s] != ATTN]


def _slice_layer(params_sub: Tree, blk: int) -> Tree:
    return jax.tree.map(lambda x: x[blk], params_sub)


@dataclass
class PrefillOutput:
    first_token: int
    k: Optional[jax.Array]           # (attn_layers, tokens, kv_dim)
    v: Optional[jax.Array]
    mamba_state: Optional[Tree]      # per (blk,sub): conv/state tensors
    prompt_len: int
    cross: Optional[Tree] = None     # enc-dec: (blk,sub) -> (xk, xv)
    # recurrent-state snapshots for the prefix store: absolute token
    # boundary -> per-(blk,sub) {"conv_x","conv_b","conv_c","state"}
    snapshots: Optional[Dict[int, Tree]] = None


class PrefillEngine:
    """Batched prefill on real params; emits per-request KV + states
    (+ cross-attention KV for encoder-decoder archs).

    ``run_suffix`` is the prefix-reuse fast path: given a gathered prefix
    KVCache it runs the forward pass over only the uncached suffix
    tokens. ``compute_tokens`` counts real prompt tokens pushed through
    the forward pass — bucket padding is tracked separately in
    ``padded_tokens`` (the parity tests and benchmarks assert savings on
    the exact counter). ``prefill_batches`` counts jitted batch
    launches; the programs they build are counted by
    ``serving/trace.py``.
    """

    def __init__(self, cfg: ModelConfig, params: Tree, *,
                 bucket_prefill: Optional[bool] = None,
                 jit_prefill: bool = True):
        self.cfg = cfg
        self.params = params
        self._attn_order = _attn_layer_order(cfg)
        self._mamba_order = _mamba_layer_order(cfg)
        # network-depth completion fraction per attention layer — static
        # per config, computed ONCE (the transfer scheduler reads it per
        # admitted request)
        period = block_period(cfg)
        total = num_blocks(cfg) * period
        self._layer_fractions: Tuple[float, ...] = tuple(
            (bk * period + sb + 1) / total for bk, sb in self._attn_order)
        if bucket_prefill is None:
            # bucketed is THE path (the REPRO_PREFILL=exact env hatch
            # was retired after the bucketed default survived three
            # releases); the constructor arg remains for measurement
            bucket_prefill = True
        # bucketing serves EVERY family: the forward is pad-invariant by
        # contract (there is no per-arch gate anymore)
        self.bucket_prefill = bool(bucket_prefill)
        self.jit_prefill = bool(jit_prefill)
        self.compute_tokens = 0      # real prompt tokens through the fwd
        self.padded_tokens = 0       # bucket-padding tokens on top
        self.reused_tokens = 0       # tokens served from a prefix hit
        self.prefix_prefills = 0     # suffix-only prefills executed
        self.state_restores = 0      # warm runs seeded from a snapshot
        self.prefill_batches = 0     # jitted batch launches
        self.chunked_prefills = 0    # prompts completed via iter_chunks
        self.chunked_chunks = 0      # individual chunk launches

    def _prefill(self, batch: Tree, *, last_index: jax.Array,
                 prefix: Optional[Tree] = None, prefix_len: int = 0,
                 ssm_init: Optional[Tree] = None, snap_stride: int = 0):
        if self.jit_prefill:
            return _jit_forward_prefill(self.cfg, self.params, batch,
                                        last_index=last_index,
                                        prefix=prefix,
                                        prefix_len=prefix_len,
                                        ssm_init=ssm_init,
                                        snap_stride=snap_stride)
        return forward_prefill(self.cfg, self.params, batch,
                               last_index=last_index, prefix=prefix,
                               prefix_len=prefix_len, ssm_init=ssm_init,
                               snap_stride=snap_stride)

    def layer_fractions(self) -> Tuple[float, ...]:
        """Network-depth completion fraction of each attention layer, in
        network order: layer li's KV is producible once frac * T_prefill
        of the batch's compute has elapsed. Static per config — the
        transfer scheduler stamps segment ready-times with these."""
        return self._layer_fractions

    def _emit_layers(self, on_layer: Optional[OnLayer], idx: int,
                     k: Optional[jax.Array], v: Optional[jax.Array]):
        """Yield one request's per-layer KV in network order."""
        if on_layer is None or k is None:
            return
        for li, frac in enumerate(self._layer_fractions):
            on_layer(idx, li, k[li], v[li], frac)

    @property
    def supports_prefix_reuse(self) -> bool:
        """Every family reuses prefixes now. Pure-attention stacks reuse
        the KV prefix alone; SSM/hybrid stacks additionally restore a
        recurrent-state snapshot cached at the reuse boundary (see
        ``requires_state_restore`` — the pool stores snapshots in
        lockstep with the KV blocks). Encoder-decoder is fine (the
        encoder reruns; only decoder self-attn KV is reused).
        Capacity-dispatch MoE is prefix-transparent since capacity went
        window-local and row-length-independent — its hits only need the
        prefix length aligned to the capacity window (``prefix_align``,
        enforced by the pool's aligned acquire).

        SSM/hybrid reuse is gated on the BUCKETED prefill path: the
        bit-identical state contract needs geometry control — a
        tiny exact-length suffix run (fewer rows than a vector tile)
        fuses/vectorizes differently and wobbles the SSD state by ulps,
        and padding it is not an option for hybrids because the warm
        attention must occupy exactly the cold run's padded key
        geometry. Under ``bucket_prefill=False`` these families simply
        serve cold, as they did before snapshots existed."""
        if self._mamba_order and not self.bucket_prefill:
            return False
        return bool(self._attn_order) or bool(self._mamba_order)

    @property
    def requires_state_restore(self) -> bool:
        """SSM/hybrid stacks: a warm hit must restore a recurrent-state
        snapshot (conv tails + SSD state) alongside any prefix KV — the
        pool only reports hits at boundaries that hold one."""
        return bool(self._mamba_order)

    @property
    def prefix_align(self) -> int:
        """Token alignment a reused prefix must satisfy. Capacity MoE
        counts expert slots in fixed windows of cfg.moe.capacity_window
        tokens: a prefix cut at a window boundary guarantees the suffix
        run sees exactly the windows a full run would give its suffix
        tokens (no capacity competition across the reuse boundary).
        Mamba layers need the cut on an SSD chunk boundary: the per-chunk
        scan carry is bitwise the state of a run truncated there, and a
        chunk-aligned restore keeps the suffix chunk partition identical
        to the cold run's. Hybrid stacks take the lcm."""
        a = 1
        m = self.cfg.moe
        if m is not None and m.dispatch == "capacity" \
                and any(self.cfg.moe_layer_mask()):
            a = m.capacity_window
        if self._mamba_order:
            a = math.lcm(a, self.cfg.ssm_cfg.chunk)
        return a

    def _bucket_len(self, n: int) -> int:
        b = PREFILL_BUCKET_MIN
        while b < n:
            b *= 2
        return min(b, max(self.cfg.max_seq_len, n))

    def run(self, token_lists: Sequence[Sequence[int]],
            frames: Optional[Sequence] = None,
            on_layer: Optional[OnLayer] = None,
            snap_stride: int = 0) -> List[PrefillOutput]:
        """Ragged batches are grouped into padded power-of-two length
        buckets for EVERY family (retrace count becomes O(num_buckets)
        under tidal ragged traffic): right padding is exact by the
        model's pad-invariance contract — causal attention masks padded
        queries, the SSD recurrence skips zero-dt pad tokens bit-exactly,
        and window-local capacity MoE routes pads to a null slot.
        (``bucket_prefill=False`` falls back to equal-length
        sub-batches for measurement.)

        ``on_layer`` enables the layer-streaming mode: each request's
        per-layer (k, v) is yielded in network order (see OnLayer) for
        per-layer-triggered transfer.

        ``snap_stride`` > 0 (static; lcm of the pool block size and the
        SSD chunk, supplied by the serving node) makes mamba sublayers
        emit recurrent-state snapshots at stride boundaries; each
        output's ``snapshots`` maps boundary -> per-layer state for the
        prefix store."""
        by_len: Dict[int, List[int]] = {}
        for i, t in enumerate(token_lists):
            key = self._bucket_len(len(t)) if self.bucket_prefill else len(t)
            by_len.setdefault(key, []).append(i)
        outs: List[Optional[PrefillOutput]] = [None] * len(token_lists)
        for ln, idxs in by_len.items():
            sub = self._run_equal(
                [token_lists[i] for i in idxs],
                [frames[i] for i in idxs] if frames is not None else None,
                pad_to=ln if self.bucket_prefill else None,
                snap_stride=snap_stride)
            for i, o in zip(idxs, sub):
                outs[i] = o
                self._emit_layers(on_layer, i, o.k, o.v)
        return outs  # type: ignore[return-value]

    def _run_equal(self, token_lists: Sequence[Sequence[int]],
                   frames: Optional[Sequence] = None,
                   pad_to: Optional[int] = None,
                   snap_stride: int = 0
                   ) -> List[PrefillOutput]:
        cfg = self.cfg
        if not self._mamba_order:
            snap_stride = 0          # snapshots are an SSM-only artifact
        b = len(token_lists)
        lens = [len(t) for t in token_lists]
        s = pad_to if pad_to is not None else max(lens)
        assert s >= max(lens), (s, lens)
        toks = np.zeros((b, s), np.int32)
        for i, t in enumerate(token_lists):
            toks[i, :len(t)] = t
        batch = {"tokens": jnp.asarray(toks)}
        self.compute_tokens += sum(lens)
        self.padded_tokens += b * s - sum(lens)
        self.prefill_batches += 1
        if cfg.is_encoder_decoder:
            assert frames is not None, "enc-dec prefill needs frames"
            batch["frames"] = jnp.stack([jnp.asarray(f) for f in frames])
        first, cache = self._prefill(
            batch, last_index=jnp.asarray([ln - 1 for ln in lens]),
            snap_stride=snap_stride)
        outs: List[PrefillOutput] = []
        layers = cache["layers"]
        for i, ln in enumerate(lens):
            if self._attn_order:
                k = jnp.stack([layers[f"sub{sb}"]["k"][bk, i, :ln]
                               for bk, sb in self._attn_order])
                v = jnp.stack([layers[f"sub{sb}"]["v"][bk, i, :ln]
                               for bk, sb in self._attn_order])
            else:
                k = v = None
            mstate: Tree = {}
            for bk, sb in self._mamba_order:
                c = layers[f"sub{sb}"]
                mstate[(bk, sb)] = {
                    "conv_x": c["conv_x"][bk, i],
                    "conv_b": c["conv_b"][bk, i],
                    "conv_c": c["conv_c"][bk, i],
                    "state": c["state"][bk, i],
                }
            cross: Optional[Tree] = None
            if cfg.is_encoder_decoder:
                cross = {}
                for bk in range(num_blocks(cfg)):
                    for sb in range(block_period(cfg)):
                        c = layers[f"sub{sb}"]
                        cross[(bk, sb)] = (c["xk"][bk, i], c["xv"][bk, i])
            snaps = self._extract_snapshots(layers, i, lens[i],
                                            snap_stride, s, base=0)
            outs.append(PrefillOutput(int(first[i]), k, v, mstate, ln,
                                      cross, snaps))
        return outs

    def _extract_snapshots(self, layers: Tree, row: int, valid: int,
                           snap_stride: int, s_pad: int, base: int
                           ) -> Optional[Dict[int, Tree]]:
        """Per-request boundary snapshots from the stacked prefill cache:
        {base + j*stride: {(blk,sub): conv tails + SSD state}} for every
        stride boundary inside the row's VALID tokens (boundaries past
        valid_len hold frozen state but pad-garbage conv rows — never
        stored). ``base`` offsets boundaries to absolute prompt
        positions for suffix-only runs."""
        if not snap_stride or not self._mamba_order:
            return None
        snaps: Dict[int, Tree] = {}
        for j in range(1, s_pad // snap_stride + 1):
            t = j * snap_stride
            if t > valid:
                break
            entry: Tree = {}
            for bk, sb in self._mamba_order:
                c = layers[f"sub{sb}"]
                entry[(bk, sb)] = {
                    "conv_x": c["snap_conv_x"][bk, j - 1, row],
                    "conv_b": c["snap_conv_b"][bk, j - 1, row],
                    "conv_c": c["snap_conv_c"][bk, j - 1, row],
                    "state": c["snap_state"][bk, j - 1, row],
                }
            snaps[base + t] = entry
        return snaps

    def run_suffix(self, suffix_tokens: Sequence[int],
                   prefix_kv: Optional[jax.Array] = None,
                   frames: Optional[object] = None,
                   on_layer: Optional[OnLayer] = None, *,
                   state: Optional[Tree] = None,
                   prefix_len: Optional[int] = None,
                   snap_stride: int = 0) -> PrefillOutput:
        """Suffix-only prefill after a prefix hit.

        ``prefix_kv``: (attn_layers, plen, 2*kv_dim) — the cached prefix
        KVCache gathered from the paged pool (kernels.kv_gather), K and V
        packed along the last axis exactly as the pool stores them; None
        for attention-free stacks (whose prefix lives entirely in
        ``state``). Runs the forward pass over only ``suffix_tokens``
        (right-padded to a length bucket — pad rows attend to nothing
        and are sliced off) with every attention sublayer attending over
        prefix ++ suffix; returns a PrefillOutput whose k/v cover the
        FULL prompt (prefix stitched back on) so the transfer/decode
        path downstream is unchanged. The prefix KV is right-padded to
        its own power-of-two bucket with the real length passed as a
        TRACED scalar (padded prefix keys are masked from every
        softmax), so warm admissions retrace per (prefix bucket, suffix
        bucket) — O(num_buckets^2) programs cluster-wide — never per
        distinct prefix length.

        ``state`` is the boundary snapshot for SSM/hybrid stacks — per
        (blk,sub) {"conv_x","conv_b","conv_c","state"} cached by the
        pool at the reuse boundary — seeding each mamba sublayer's conv
        windows and SSD scan so the suffix run continues the recurrence
        bitwise (the returned ``mamba_state`` is the RESTORED state
        advanced over the suffix, ready for decode hand-off / transfer).
        ``prefix_len`` is required when ``prefix_kv`` is None.
        ``snap_stride`` > 0 additionally emits new snapshots over the
        suffix, reported at ABSOLUTE boundaries in ``out.snapshots``.
        """
        cfg = self.cfg
        assert self.supports_prefix_reuse, cfg.name
        if self.requires_state_restore:
            assert state is not None, \
                f"{cfg.name}: SSM warm hit needs a state snapshot"
        s = len(suffix_tokens)
        assert s >= 1, "prefix hit must leave at least one suffix token"
        plen = int(prefix_kv.shape[1]) if prefix_kv is not None \
            else int(prefix_len)
        if prefix_kv is not None and self._mamba_order and \
                self.bucket_prefill:
            # hybrid (attn + SSM) warm runs carry a BITWISE state-parity
            # contract: XLA's key-axis reduction tiling depends on the
            # padded length, so the warm softmax/PV matmul only
            # reproduces the cold run bit-for-bit when prefix ++ suffix
            # keys occupy exactly the geometry the cold run padded to —
            # prefix at its true (aligned) length, suffix padded so the
            # total lands on the cold bucket of the full prompt.
            s_pad = self._bucket_len(plen + s) - plen
        else:
            s_pad = self._bucket_len(s) if self.bucket_prefill else s
        assert prefix_len is None or int(prefix_len) == plen
        # capacity-MoE / SSD-chunk prefix hits must land on aligned
        # boundaries (the pool's aligned acquire guarantees this; a
        # misaligned prefix would shift the suffix's capacity windows
        # or de-align the suffix SSD chunk partition)
        assert plen % self.prefix_align == 0, (plen, self.prefix_align)
        period = block_period(cfg)
        nblk = num_blocks(cfg)
        prefix: Optional[Tree] = None
        k_pre = v_pre = None
        p_pad = 0
        if prefix_kv is not None:
            # hybrid: prefix stays at its exact aligned length (see the
            # s_pad choice above); attn-only keeps the O(buckets^2)
            # prefix-bucket scheme
            p_pad = plen if self._mamba_order else (
                self._bucket_len(plen) if self.bucket_prefill else plen)
            if p_pad != plen:
                prefix_kv = jnp.pad(prefix_kv,
                                    ((0, 0), (0, p_pad - plen), (0, 0)))
            kvd = cfg.kv_dim
            k_pre, v_pre = prefix_kv[..., :kvd], prefix_kv[..., kvd:]
            attn_idx = {pair: li
                        for li, pair in enumerate(self._attn_order)}
            prefix = {}
            for sb in range(period):
                if (0, sb) not in attn_idx:
                    prefix[f"sub{sb}"] = {}   # mamba sub: state, not KV
                    continue
                ks = jnp.stack([k_pre[attn_idx[(bk, sb)]]
                                for bk in range(nblk)])
                vs = jnp.stack([v_pre[attn_idx[(bk, sb)]]
                                for bk in range(nblk)])
                # (num_blocks, b=1, p_pad, kv_dim), scanned with params
                prefix[f"sub{sb}"] = {"k": ks[:, None], "v": vs[:, None]}
        ssm_init: Optional[Tree] = None
        if state is not None:
            mamba_subs = {sb for _, sb in self._mamba_order}
            ssm_init = {}
            for sb in range(period):
                if sb not in mamba_subs:
                    ssm_init[f"sub{sb}"] = {}
                    continue
                # stack snapshot leaves over blocks, batch dim 1 — exact
                # dtypes preserved (restore must be bitwise)
                ssm_init[f"sub{sb}"] = {
                    k2: jnp.stack([jnp.asarray(state[(bk, sb)][k2])[None]
                                   for bk in range(nblk)])
                    for k2 in ("conv_x", "conv_b", "conv_c", "state")}
        toks = list(suffix_tokens) + [0] * (s_pad - s)
        batch = {"tokens": jnp.asarray([toks], jnp.int32)}
        if cfg.is_encoder_decoder:
            assert frames is not None, "enc-dec prefill needs frames"
            batch["frames"] = jnp.asarray(frames)[None]
        first, cache = self._prefill(
            batch, last_index=jnp.asarray([s - 1]), prefix=prefix,
            prefix_len=jnp.asarray(plen, jnp.int32), ssm_init=ssm_init,
            snap_stride=snap_stride if self._mamba_order else 0)
        self.compute_tokens += s
        self.padded_tokens += (s_pad - s) + (p_pad - plen if p_pad else 0)
        self.reused_tokens += plen
        self.prefix_prefills += 1
        if state is not None:
            self.state_restores += 1
        self.prefill_batches += 1
        layers = cache["layers"]
        k = v = None
        if self._attn_order:
            k_suf = jnp.stack([layers[f"sub{sb}"]["k"][bk, 0, :s]
                               for bk, sb in self._attn_order])
            v_suf = jnp.stack([layers[f"sub{sb}"]["v"][bk, 0, :s]
                               for bk, sb in self._attn_order])
            # stitch with the REAL prefix rows only (bucket pads sliced
            # off): no KV row past the ledgered compute/reused tokens
            # survives
            k = jnp.concatenate([k_pre[:, :plen].astype(k_suf.dtype),
                                 k_suf], axis=1)
            v = jnp.concatenate([v_pre[:, :plen].astype(v_suf.dtype),
                                 v_suf], axis=1)
        mstate: Tree = {}
        for bk, sb in self._mamba_order:
            c = layers[f"sub{sb}"]
            mstate[(bk, sb)] = {
                "conv_x": c["conv_x"][bk, 0],
                "conv_b": c["conv_b"][bk, 0],
                "conv_c": c["conv_c"][bk, 0],
                "state": c["state"][bk, 0],
            }
        cross: Optional[Tree] = None
        if cfg.is_encoder_decoder:
            cross = {}
            for bk in range(nblk):
                for sb in range(period):
                    c = layers[f"sub{sb}"]
                    cross[(bk, sb)] = (c["xk"][bk, 0], c["xv"][bk, 0])
        snaps = self._extract_snapshots(
            layers, 0, s, snap_stride if self._mamba_order else 0,
            s_pad, base=plen)
        out = PrefillOutput(int(first[0]), k, v, mstate, plen + s, cross,
                            snaps)
        # stream the FULL prompt's layers (prefix stitched back on): the
        # receiver's layout is identical to a cold prefill's
        self._emit_layers(on_layer, 0, k, v)
        return out

    # ------------------------------------------------- chunked prefill
    def chunk_bounds(self, n: int, chunk_tokens: int) -> List[int]:
        """Interior cut points for a chunked prefill of an ``n``-token
        prompt. Cuts land on ``prefix_align`` boundaries (the same
        contract the prefix store's aligned acquire enforces) and the
        final chunk always keeps >= 1 token, so each continuation is a
        legal ``run_suffix``."""
        align = max(self.prefix_align, 1)
        step = max(align, (int(chunk_tokens) // align) * align)
        return list(range(step, n, step))

    def iter_chunks(self, tokens: Sequence[int], *, chunk_tokens: int,
                    frames: Optional[object] = None):
        """DynaServe-style chunked prefill: run the prompt as a cold
        first chunk followed by ``run_suffix`` continuations, threading
        the stitched KV and (for SSM/hybrid stacks) the advanced
        recurrent state across chunks. Yields ``(n_chunk_tokens, out)``
        after each chunk so an event-driven caller can interleave other
        work (decode steps) between chunks; the final yield's output
        covers the full prompt and is token-identical to
        ``run([tokens])[0]`` — it is the identical warm-continuation
        machinery the prefix store's bitwise contracts already pin."""
        assert self.supports_prefix_reuse, self.cfg.name
        toks = list(tokens)
        n = len(toks)
        cuts = [0] + self.chunk_bounds(n, chunk_tokens) + [n]
        out: Optional[PrefillOutput] = None
        for lo, hi in zip(cuts, cuts[1:]):
            chunk = toks[lo:hi]
            if lo == 0:
                out = self.run(
                    [chunk],
                    frames=[frames] if frames is not None else None)[0]
            else:
                pkv = None
                if out.k is not None:
                    pkv = jnp.concatenate([out.k, out.v], axis=-1)
                out = self.run_suffix(
                    chunk, prefix_kv=pkv, frames=frames,
                    state=out.mamba_state
                    if self.requires_state_restore else None,
                    prefix_len=lo)
            self.chunked_chunks += 1
            yield hi - lo, out
        self.chunked_prefills += 1

    def run_chunked(self, tokens: Sequence[int], *, chunk_tokens: int,
                    frames: Optional[object] = None) -> PrefillOutput:
        """Drain ``iter_chunks``; returns the full-prompt output."""
        out: Optional[PrefillOutput] = None
        for _, out in self.iter_chunks(tokens, chunk_tokens=chunk_tokens,
                                       frames=frames):
            pass
        assert out is not None
        return out


class DecodeEngine:
    """Continuous-batched paged decode over a PagedKVPool.

    Slot state lives in fixed-shape padded arrays over ``max_slots``
    (tokens / positions / active mask / power-of-two bucketed block
    table, plus block-stacked mamba and cross-attention buffers from
    ``caches.decode_slot_state``), so the fused path runs the whole
    iteration as one jitted device program with the pool storage and
    slot buffers donated: one dispatch, one host transfer (the argmax),
    zero per-layer pool copies. Retraces happen only when the block
    table grows past its bucket (bounded by log2(pool blocks)).

    ``fused=False`` keeps the eager per-layer loop: one dispatch per
    sublayer, a whole-pool copy per attention layer, a host sync per
    step — the measured baseline in benchmarks/bench_decode.py.

    ``spec=`` (a ``SpecConfig``) switches the fused step to the
    speculative propose/verify program
    (``models.modeling.spec_decode_step_jit``): draft and target run in
    ONE donated program and each slot retires 1..k+1 tokens per step
    (``step()`` then maps slots to token LISTS). The draft's paged KV
    rides the target's block tables in an engine-owned storage array,
    its recurrent/cross state in a second donated slot-state carry, and
    its prompt is prefilled at admission by an engine-owned draft
    PrefillEngine — the decode node never sees two models. Greedy
    speculation is lossless, so the emitted stream (and the paged pool,
    bit-for-bit) matches plain fused greedy decode.
    """

    def __init__(self, cfg: ModelConfig, params: Tree, pool: PagedKVPool,
                 *, max_slots: int = 8, fused: Optional[bool] = None,
                 spec: Optional[SpecConfig] = None):
        self.cfg = cfg
        self.params = params
        self.pool = pool
        self.max_slots = max_slots
        self.fused = True if fused is None else bool(fused)
        self.spec = spec
        if spec is not None:
            assert self.fused, "speculative decode requires the fused step"
            assert not cfg.is_encoder_decoder, \
                "speculative decode does not cover enc-dec families yet"
            d_attn = len(_attn_layer_order(spec.draft_cfg))
            self._d_storage = jnp.zeros(
                (max(d_attn, 1), pool.num_blocks, pool.block_size,
                 2 * spec.draft_cfg.kv_dim), pool.dtype)
            self._d_slot_layers = decode_slot_state(spec.draft_cfg,
                                                    max_slots)
            # cold draft prompt prefill at admission (the draft has no
            # prefix store; its whole cache is rebuilt per admission)
            self._d_prefill = PrefillEngine(spec.draft_cfg,
                                            spec.draft_params)
        self._attn_order = _attn_layer_order(cfg)
        self._mamba_order = _mamba_layer_order(cfg)
        # slot state: host mirrors (admission bookkeeping) ...
        self.rid = [None] * max_slots
        self.pos = np.zeros(max_slots, np.int64)      # tokens so far
        self.last_tok = np.zeros(max_slots, np.int32)
        # ... and fixed-shape device state for the fused step
        self._slot_layers = decode_slot_state(cfg, max_slots)
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._pos = jnp.zeros((max_slots,), jnp.int32)
        self._active = jnp.zeros((max_slots,), bool)
        self._table_w = 1                             # pow2 table bucket
        self._table = jnp.full((max_slots, 1), -1, jnp.int32)
        self._caps = np.zeros(max_slots, np.int64)    # tokens allocatable
        self._caps_dev = jnp.zeros((max_slots,), jnp.int32)
        self._dirty = True        # host mirrors ahead of device arrays
        self.fused_steps = 0
        self.eager_steps = 0
        self.spec_steps = 0       # fused speculative iterations
        self.spec_emitted = 0     # tokens retired by those iterations

    # ------------------------------------------------------------- slots
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.rid) if r is None]

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.rid) if r is not None]

    def admit(self, rid: int, out: PrefillOutput, blocks: Sequence[int],
              slot: Optional[int] = None,
              prompt: Optional[Sequence[int]] = None) -> int:
        """Attach a transferred request to a free slot. The KV for its
        prompt must already be in `self.pool` under `blocks`, and the
        request's FULL block allocation (prompt + generation room) must
        be in place — the fused step snapshots the block table here.

        In ``spec=`` mode the caller must also pass the request's
        ``prompt`` tokens: the draft model sees no transferred KV (only
        the target's prefill crossed the wire), so the engine prefills
        the draft here and seeds its KV/recurrent slot state alongside
        the target's."""
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free decode slot")
            slot = free[0]
        self.rid[slot] = rid
        self.pos[slot] = out.prompt_len
        self.last_tok[slot] = out.first_token
        for (bk, sb), st in (out.mamba_state or {}).items():
            buf = self._slot_layers[f"sub{sb}"]
            for k2 in ("conv_x", "conv_b", "conv_c", "state"):
                buf[k2] = buf[k2].at[bk, slot].set(
                    st[k2].astype(buf[k2].dtype))
        for (bk, sb), (xk, xv) in (out.cross or {}).items():
            buf = self._slot_layers[f"sub{sb}"]
            buf["xk"] = buf["xk"].at[bk, slot].set(xk.astype(buf["xk"].dtype))
            buf["xv"] = buf["xv"].at[bk, slot].set(xv.astype(buf["xv"].dtype))
        if self.spec is not None:
            if prompt is None:
                raise ValueError(
                    "spec-mode admission needs the prompt tokens (the "
                    "draft model prefills here, at the decode node)")
            self._admit_draft(slot, list(prompt), blocks)
        self._dirty = True
        return slot

    def _admit_draft(self, slot: int, prompt: List[int],
                     blocks: Sequence[int]):
        """Cold draft prompt prefill + slot seeding: draft KV is written
        into the engine-owned draft storage at the TARGET's blocks (the
        draft rides the target's block tables), draft recurrent state
        into the draft slot-state carry."""
        d_out = self._d_prefill.run([prompt])[0]
        if d_out.k is not None:
            bs = self.pool.block_size
            toks = np.arange(d_out.prompt_len)
            blk = jnp.asarray(np.asarray(list(blocks))[toks // bs])
            off = jnp.asarray(toks % bs)
            kv = jnp.concatenate([d_out.k, d_out.v],
                                 axis=-1).astype(self._d_storage.dtype)
            self._d_storage = self._d_storage.at[:, blk, off].set(kv)
        for (bk, sb), st in (d_out.mamba_state or {}).items():
            buf = self._d_slot_layers[f"sub{sb}"]
            for k2 in ("conv_x", "conv_b", "conv_c", "state"):
                buf[k2] = buf[k2].at[bk, slot].set(
                    st[k2].astype(buf[k2].dtype))

    def evict(self, slot: int):
        self.rid[slot] = None
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        self._dirty = True

    def evict_all(self) -> List[int]:
        """Clear every active slot in one sweep — the crash-recovery
        wipe (serving/faults.py): a dead/ejected node's in-flight
        requests are re-admitted elsewhere, so its slot state must not
        survive into a rejoin."""
        slots = self.active_slots()
        for s in slots:
            self.evict(s)
        return slots

    # -------------------------------------------------------------- step
    def step(self) -> Dict[int, int]:
        """One decode iteration over all active slots.
        Returns {slot: next_token} — or, in ``spec=`` mode,
        {slot: [token, ...]} with 1..k+1 tokens retiring per slot."""
        if self.spec is not None:
            return self._step_spec()
        if self.fused:
            return self._step_fused()
        return self._step_eager()

    def _sync_device(self):
        """Push host slot mirrors into the fixed-shape device arrays.
        Runs only after admissions/evictions (membership changes) — the
        steady-state fused loop touches no host state on the way in."""
        with trace.span("pd.decode.upload"):
            need = max((len(self.pool.owned(r)) for r in self.rid
                        if r is not None), default=1)
            while self._table_w < need:
                self._table_w *= 2
            self._tokens = jnp.asarray(self.last_tok)
            self._pos = jnp.asarray(self.pos.astype(np.int32))
            self._active = jnp.asarray(
                np.asarray([r is not None for r in self.rid]))
            self._table = jnp.asarray(
                self.pool.block_tables(list(self.rid), self._table_w))
            bs = self.pool.block_size
            self._caps = np.asarray(
                [len(self.pool.owned(r)) * bs if r is not None else 0
                 for r in self.rid], np.int64)
            self._caps_dev = jnp.asarray(self._caps.astype(np.int32))
            self._dirty = False

    def _step_fused(self) -> Dict[int, int]:
        act = self.active_slots()
        if not act:
            return {}
        if self._dirty:
            self._sync_device()
        # the device scatter clamps indices, which would silently
        # overwrite earlier KV on allocation overflow — fail loudly like
        # the eager loop's Python indexing instead (caps snapshotted at
        # sync: allocations are fixed from admit onward)
        over = np.nonzero(self.pos >= self._caps)[0]
        over = [s for s in over if self.rid[s] is not None]
        if over:
            s_i = over[0]
            raise IndexError(
                f"slot {s_i} (rid {self.rid[s_i]}): token position "
                f"{int(self.pos[s_i])} outside its "
                f"{int(self._caps[s_i])}-token block allocation")
        nxt, toks, pos, storage, layers = decode_step_jit(
            self.cfg, self.params, self.pool.storage, self._table,
            self._tokens, self._pos, self._active, self._slot_layers,
            block_size=self.pool.block_size)
        self.pool.set_storage(storage)       # donated: updated in place
        self._slot_layers = layers
        self._tokens, self._pos = toks, pos
        self.fused_steps += 1
        with trace.span("pd.decode.readback"):
            out_np = np.asarray(nxt)         # the ONE host sync per step
        out: Dict[int, int] = {}
        for s_i in act:
            self.pos[s_i] += 1
            self.last_tok[s_i] = out_np[s_i]
            out[s_i] = int(out_np[s_i])
        return out

    def _step_spec(self) -> Dict[int, List[int]]:
        """One fused speculative iteration: {slot: emitted tokens},
        1..k+1 per active slot. Mirrors ``_step_fused`` — same loud
        overflow check, same donation adoption, still exactly ONE
        device->host transfer (the packed (slots, k+2) out matrix)."""
        act = self.active_slots()
        if not act:
            return {}
        if self._dirty:
            self._sync_device()
        over = np.nonzero(self.pos >= self._caps)[0]
        over = [s for s in over if self.rid[s] is not None]
        if over:
            s_i = over[0]
            raise IndexError(
                f"slot {s_i} (rid {self.rid[s_i]}): token position "
                f"{int(self.pos[s_i])} outside its "
                f"{int(self._caps[s_i])}-token block allocation")
        k = self.spec.k
        (packed, toks, pos, storage, d_storage, layers,
         d_layers) = spec_decode_step_jit(
            self.cfg, self.spec.draft_cfg, self.params,
            self.spec.draft_params, self.pool.storage, self._d_storage,
            self._table, self._tokens, self._pos, self._active,
            self._caps_dev, self._slot_layers, self._d_slot_layers,
            block_size=self.pool.block_size, k=k)
        self.pool.set_storage(storage)       # donated: updated in place
        self._d_storage = d_storage
        self._slot_layers, self._d_slot_layers = layers, d_layers
        self._tokens, self._pos = toks, pos
        self.fused_steps += 1
        self.spec_steps += 1
        out_np = np.asarray(packed)          # the ONE host sync per step
        out: Dict[int, List[int]] = {}
        for s_i in act:
            n = int(out_np[s_i, k + 1])
            emit = [int(t) for t in out_np[s_i, :n]]
            self.pos[s_i] += n
            self.last_tok[s_i] = emit[-1]
            self.spec_emitted += n
            out[s_i] = emit
        return out

    def _step_eager(self) -> Dict[int, int]:
        """Legacy per-layer loop (benchmark baseline): every sublayer is
        a separate dispatch and each attention layer swaps a full copy
        of the paged pool."""
        cfg = self.cfg
        act = self.active_slots()
        if not act:
            return {}
        act_arr = np.asarray(act)
        toks = jnp.asarray(self.last_tok[act_arr])
        pos = jnp.asarray(self.pos[act_arr])          # tokens so far
        h = self.params["embed"][toks].astype(jnp.float32)
        period = block_period(cfg)
        kinds = cfg.layer_kinds()
        moe_mask = cfg.moe_layer_mask()
        attn_idx = {pair: i for i, pair in enumerate(self._attn_order)}
        # block tables sized to the largest allocation among active slots
        nblocks = max(len(self.pool.owned(self.rid[s])) for s in act)
        bt = jnp.asarray(self.pool.block_tables(
            [self.rid[s] for s in act], nblocks))
        lens = pos + 1                                 # incl. current token
        for bk in range(num_blocks(cfg)):
            for sb in range(period):
                p = _slice_layer(self.params["blocks"][f"sub{sb}"], bk)
                if kinds[sb] == ATTN:
                    li = attn_idx[(bk, sb)]
                    x = rmsnorm(h, p["norm"], cfg.norm_eps)
                    q, k, v = _attn_proj_qkv(p, x[:, None, :], cfg)
                    q4 = _split_heads(q[:, 0], cfg.num_heads)
                    k4 = _split_heads(k[:, 0], cfg.num_kv_heads)
                    q4 = rope(q4, pos, cfg.rope_theta)
                    k4 = rope(k4, pos, cfg.rope_theta)
                    kf, vf = _merge_heads(k4), v[:, 0]
                    # write the token into the pool at (block, offset)
                    blk_ids, offs = [], []
                    for s_i in act:
                        bl = self.pool.owned(self.rid[s_i])
                        t = int(self.pos[s_i])
                        blk_ids.append(bl[t // self.pool.block_size])
                        offs.append(t % self.pool.block_size)
                    kv_tok = jnp.concatenate([kf, vf], -1).astype(
                        self.pool.dtype)
                    self.pool.set_storage(self.pool.storage.at[
                        li, jnp.asarray(blk_ids), jnp.asarray(offs)
                    ].set(kv_tok))
                    o = ops.paged_attention(
                        q4.astype(self.pool.dtype),
                        self.pool.storage[li], bt,
                        lens.astype(jnp.int32))
                    h = h + _merge_heads(o).astype(h.dtype) @ p["wo"]
                else:
                    buf = self._slot_layers[f"sub{sb}"]
                    cin = {k2: buf[k2][bk, act_arr]
                           for k2 in ("conv_x", "conv_b", "conv_c",
                                      "state")}
                    h, nc = mamba_sublayer_step(p, h, cin, cfg)
                    for k2, v2 in nc.items():
                        buf[k2] = buf[k2].at[bk, act_arr].set(
                            v2.astype(buf[k2].dtype))
                if cfg.is_encoder_decoder:
                    from repro.models.modeling import attention_decode
                    buf = self._slot_layers[f"sub{sb}"]
                    xk = buf["xk"][bk, act_arr]
                    xv = buf["xv"][bk, act_arr]
                    x = rmsnorm(h, p["norm_x"], cfg.norm_eps)
                    q4 = _split_heads(x @ p["wqx"], cfg.num_heads)
                    o = attention_decode(
                        q4.astype(jnp.float32), xk, xv,
                        cfg.num_kv_heads,
                        jnp.asarray(cfg.encoder_seq), window=None)
                    h = h + _merge_heads(o).astype(h.dtype) @ p["wox"]
                h2, _ = _ffn_sublayer(p, h[:, None, :], cfg, moe_mask[sb])
                h = h2[:, 0]
        h = rmsnorm(h, self.params["final_norm"], cfg.norm_eps)
        logits = lm_logits(cfg, self.params, h)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.eager_steps += 1
        self._dirty = True       # device token/pos mirrors are now stale
        out: Dict[int, int] = {}
        for j, s_i in enumerate(act):
            self.pos[s_i] += 1
            self.last_tok[s_i] = nxt[j]
            out[s_i] = int(nxt[j])
        return out
