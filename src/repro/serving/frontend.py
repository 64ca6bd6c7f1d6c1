"""Scenario-aware multi-group serving frontend on the REAL data path.

This is the paper's fine-grained P/D organization (§3.2-3.5) running on
actual engines rather than the discrete-event simulator:

  ClusterFrontend (gateway)
    -> ServeGroup["svcA/chat"]: PrefillNode* -> KV transfer -> DecodeNode*
    -> ServeGroup["svcA/summ"]: PrefillNode* -> KV transfer -> DecodeNode*
    ...

Each ServeGroup binds one scenario tag to its own prefill/decode nodes
registered in the MetaStore (the Zookeeper role), so prefill/decode
processing stays similar within a group — and the group's prefill pools
keep that scenario's prefix KVCaches hot (§2.2.1): ingress prefers the
node with the longest cached prefix (suffix-only prefill on a hit, see
serving/kvcache.py), then least SSE connections, with on-demand
rejection forwarding across groups when the home group is saturated
(§3.5 fallback), else the request waits at the gateway.
ServeGroup.prefix_stats() aggregates hit-rate / reused-token counters.

The serving core is TICKLESS: the TransferScheduler's virtual-time
event queue is the spine of the group. Request arrivals, prefill-batch
completions, per-layer KV segment landings, decode steps and drained
role flips are all timestamped events drained in
nondecreasing virtual time (ClusterFrontend.serve merges every group's
frontier plus the gateway arrival queue onto one shared timeline), so
TTFT/TPOT are ledgered in virtual SECONDS — the goodput currency of the
open-loop benchmarks — not in synchronous tick counts. The staged
``tick()`` survives only as a compatibility shim that pumps the same
event handlers in the legacy stage order (prefill -> transfer -> pump
-> decode) to the current deadline; both paths are token-identical
(greedy decode is scheduling-order-invariant, pinned by test).

KV hand-off runs through the overlapped layer-wise transfer pipeline by
default (serving/transfer_sched.py, §3.6 Fig. 10): prefill streams
per-layer KV into the scheduler, decode admission fires when the last
segment lands, and per-group transfer_stats() ledgers admission waits,
retries and failover requeues. ``overlap_transfer=False`` restores the
blocking transfer (charged on the same event timeline).

A RatioAdjuster performs runtime P/D ratio adjustment per group: it
compares the deployed ratio against the Eq.1 optimum
(repro.core.perf_model.optimal_ratio) on a profiled-in-advance
InstanceProfile or on the group's own observed prefill/decode timings,
gated by observed queue/TTFT pressure, then flips ONE node between P
and D roles. A flip drains the node first (logical removal: no new
traffic, in-flight work completes), then swaps the
PrefillNode/DecodeNode wrapper over the SAME shared params and
re-registers the instance in the MetaStore — PDGroup's dynamic RoCE
reconstruction (core.group), but on real engines.
"""
from __future__ import annotations

import heapq
import itertools
import random
import time
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.perf_model import InstanceProfile, optimal_ratio
from repro.core.transfer import KVTransferEngine, LinkModel
from repro.core.zookeeper import MetaStore
from repro.models.config import ModelConfig
from repro.models.params import init_params
from repro.serving import trace
from repro.serving.cluster import DecodeNode, PrefillNode, ServeRequest
from repro.serving.engine import prefill_compile_count
from repro.serving.transfer_sched import (TransferJob, TransferScheduler,
                                          state_payload_nbytes)


def _mean(xs: Sequence[float]) -> float:
    return sum(xs) / len(xs) if xs else 0.0


def _median(xs: Sequence[float]) -> float:
    """True median: even-length windows average the two middle samples
    (the upper-middle shortcut biased Eq.1 inputs and the *_median_s
    telemetry high)."""
    if not xs:
        return 0.0
    s = sorted(xs)
    n = len(s)
    if n % 2:
        return s[n // 2]
    return 0.5 * (s[n // 2 - 1] + s[n // 2])


@dataclass
class AbsorbJob:
    """One chunked prefill running ON a decode node (DynaServe-style
    elasticity): ``chunks`` is the engine's ``iter_chunks`` generator,
    stepped one chunk per ``absorb`` event so decode steps interleave
    between chunks on the virtual timeline. The node's pool blocks for
    prompt + generation were reserved at job start; the final chunk's
    stitched KV is written there and the request admits in place — no
    transfer, the KV is already home."""
    req: ServeRequest
    node: DecodeNode
    chunks: object                  # PrefillEngine.iter_chunks generator
    n_left: int                     # chunks not yet run
    out: object = None              # latest (cumulative) PrefillOutput
    dead: bool = False              # node crashed/ejected under the job


class ServeGroup:
    """One scenario-bound P/D group on real engines (paper §3.2-3.3).

    Internally event-driven: ``self.events`` is a (t, seq, kind, node)
    min-heap sharing one virtual timeline with the TransferScheduler's
    link events. Event kinds:

      * ``batch``   — a prefill node runs its formed batch (charging its
                      MEASURED wall time as virtual seconds);
      * ``xfer``    — hand prefilled requests to decode (begin pipelined
                      transfer, or pay the blocking stall inline);
      * ``step``    — one continuous-batching decode iteration
                      (self-rescheduling while the node has requests);
      * ``segment`` — a per-layer KV stripe (or trailing state payload)
                      landed on a link (drained via scheduler pump);
      * ``pump``    — bare scheduler retry point (waiting_dst jobs).

    ``event_log`` records drained events as (t, kind), nondecreasing in
    t while the tickless loop drives the group (property-tested)."""

    def __init__(self, gid: str, scenario: str, cfg: ModelConfig, params,
                 meta: MetaStore, xfer: KVTransferEngine, *,
                 n_prefill: int = 1, n_decode: int = 1,
                 transfer_mode: str = "block_free",
                 overlap_transfer: bool = True,
                 iid_prefix: Optional[str] = None,
                 prefill_kwargs: Optional[dict] = None,
                 decode_kwargs: Optional[dict] = None,
                 spec=None, fault_plan=None,
                 fault_kwargs: Optional[dict] = None,
                 service_model=None,
                 absorb_prefill: bool = False,
                 absorb_chunk_tokens: int = 16):
        self.gid = gid
        self.scenario = scenario
        self.cfg = cfg
        self.params = params
        self.meta = meta
        self.xfer = xfer
        self.transfer_mode = transfer_mode
        # overlapped layer-wise transfer pipeline (Fig. 10): decode
        # admission is event-driven (fires when the last layer lands)
        # instead of blocking inside the transfer hand-off
        self.overlap_transfer = bool(overlap_transfer)
        self.sched: Optional[TransferScheduler] = TransferScheduler(
            xfer.link, seed=zlib.crc32(gid.encode()) & 0xFFFF,
            pick_dst=self._sched_pick) if overlap_transfer else None
        self.vclock = 0.0                          # virtual seconds
        self.blocking_waits: List[float] = []      # sync-mode D2D stalls
        self.n_blocking_admits = 0                 # monotonic (list trims)
        self._blk_free_t = 0.0                     # blocking-mode link busy
        self.prefill_kwargs = dict(prefill_kwargs or {})
        self.decode_kwargs = dict(decode_kwargs or {})
        # group-wide speculative draft binding: every decode node this
        # group ever constructs (including P->D role flips) runs the
        # same scenario-chosen draft
        if spec is not None:
            self.decode_kwargs.setdefault("spec", spec)
        self._prefix = f"{gid}/" if iid_prefix is None else iid_prefix
        self._n_p = itertools.count()
        self._n_d = itertools.count()
        meta.register_group(gid, scenario)
        self.prefills: List[PrefillNode] = [
            self._new_prefill(0.0) for _ in range(n_prefill)]
        self.decodes: List[DecodeNode] = [
            self._new_decode(0.0) for _ in range(n_decode)]
        self.rejections = 0            # requests no node would take (§3.5)
        self.probe_rejections = 0      # per-node placement probes that failed
        self.n_accepted = 0
        self.accepted: List[int] = []              # recent rids admitted
        # (t, old_iid, new_iid, "P->D" | "D->P"); t is the tick number
        # under the staged shim, virtual seconds under the event loop.
        # The list keeps a bounded window; n_flips is the monotonic count
        self.flips: List[Tuple[float, str, str, str]] = []
        self.n_flips = 0
        # ------------------------------------- autoscale / elasticity
        self.scaler = None             # AutoScaler back-ref (scale events)
        self.scale_op = None           # in-flight ScaleOp (adjuster yields)
        self.absorb_prefill = bool(absorb_prefill)
        self.absorb_chunk_tokens = int(absorb_chunk_tokens)
        self.absorb_retry_s = 2e-3     # slot-wait poll for the final chunk
        self.absorbs: Dict[str, int] = {
            "absorb_requests": 0, "absorb_chunks": 0,
            "absorb_tokens": 0, "absorb_displaced": 0}
        self.on_displaced = None       # gateway hook: crashed absorb jobs
        # observed stats feeding the ratio adjuster; consumers only read
        # bounded tails, so the event handlers trim these to a window
        self.prefill_batch_s: List[float] = []     # wall time per batch
        self.decode_step_s: List[float] = []       # wall time per step
        self.gen_tokens: List[int] = []            # admitted target lengths
        self.ttft_s: List[float] = []              # submit -> first token
        # ------------------------------------------------- event core
        self.events: List[Tuple[float, int, str, object]] = []
        self._eseq = itertools.count()
        self.event_log: List[Tuple[float, str]] = []
        self._tickless = False         # True while ClusterFrontend.serve
        self.on_capacity = None        # gateway hook: capacity may have freed
        # ------------------------------------------- fault tolerance
        # deterministic virtual service-time model (faults.py): when
        # set, batch/step events charge model costs instead of measured
        # wall time, making the whole event log bit-reproducible
        self.service_model = service_model
        self.ft = None                 # FaultTolerance controller
        if fault_plan is not None:
            from repro.serving.faults import FaultTolerance
            self.ft = FaultTolerance(self, fault_plan,
                                     **(fault_kwargs or {}))

    # ------------------------------------------------- node construction
    def _set_class(self, node, ncls):
        if ncls is not None:
            node.node_class = ncls.name
            node.prefill_scale = ncls.prefill_scale
            node.decode_scale = ncls.decode_scale
        return node

    def _new_prefill(self, t: float, *, iid: Optional[str] = None,
                     ncls=None) -> PrefillNode:
        iid = iid or f"{self._prefix}P{next(self._n_p)}"
        node = PrefillNode(iid, self.cfg, self.params,
                           **self.prefill_kwargs)
        self.meta.gather_instance(t, iid, "P", self.gid)
        self.meta.health_report(t, iid)
        return self._set_class(node, ncls)

    def _new_decode(self, t: float, *, iid: Optional[str] = None,
                    ncls=None) -> DecodeNode:
        iid = iid or f"{self._prefix}D{next(self._n_d)}"
        node = DecodeNode(iid, self.cfg, self.params, **self.decode_kwargs)
        self.meta.gather_instance(t, iid, "D", self.gid)
        self.meta.health_report(t, iid)
        return self._set_class(node, ncls)

    # ---------------------------------------- autoscale node lifecycle
    def find_node(self, iid: str):
        for n in self.prefills + self.decodes:
            if n.iid == iid:
                return n
        return None

    def add_node(self, t: float, role: str, *, iid: Optional[str] = None,
                 ncls=None):
        """Provisioned capacity joins the group (the terminal event of a
        scale-up op): the node registers in the MetaStore, fresh
        capacity retries stranded hand-offs and pending gateway work."""
        if role == "P":
            node = self._new_prefill(t, iid=iid, ncls=ncls)
            node.busy_until = t
            self.prefills.append(node)
        else:
            node = self._new_decode(t, iid=iid, ncls=ncls)
            node.busy_until = t
            self.decodes.append(node)
        self.event_log.append((t, "scale"))
        if self._tickless:
            for p in self.prefills:
                if p.waiting:
                    self.schedule(t, "xfer", p)
            if self.sched is not None and not self.sched.idle():
                self.schedule(t, "pump", None)
        if self.on_capacity is not None:
            self.on_capacity(t)
        return node

    def node_drained(self, node) -> bool:
        """No in-flight work left on a draining node (decommission can
        complete)."""
        if node in self.prefills:
            return not (node.forming or node.waiting)
        busy = bool(node.requests) or node._absorb_job is not None
        if self.sched is not None and self.sched.pending_for(node.iid):
            busy = True
        return not busy

    def remove_node(self, t: float, node):
        """Decommission a drained node out of the group (back to the
        shared pool — the AutoScaler owns the pool-side accounting)."""
        if node in self.prefills:
            self.prefills.remove(node)
        elif node in self.decodes:
            self.decodes.remove(node)
        self.meta.remove_instance(t, node.iid)
        self.event_log.append((t, "scale"))

    @property
    def ratio(self) -> Tuple[int, int]:
        return len(self.prefills), len(self.decodes)

    def load(self) -> int:
        """Requests currently anywhere in this group's pipeline (forming
        or prefilled-but-unhanded, in-flight transfer, decoding) — the
        gateway's least-loaded fallback signal for unknown scenarios."""
        n = sum(len(p.forming) + len(p.waiting) for p in self.prefills)
        n += sum(len(d.requests) for d in self.decodes)
        n += sum(1 for d in self.decodes if d._absorb_job is not None)
        if self.sched is not None:
            n += len(self.sched.jobs) + len(self.sched.waiting)
        return n

    # ------------------------------- ingress (on-demand rejection, §3.5)
    def offer(self, req: ServeRequest, t: Optional[float] = None) -> bool:
        """Place ``req`` on a prefill node. ONE rejection is counted per
        request no node accepts (per-node probe failures are ledgered
        separately — the old per-probe count inflated §3.5 forwarding
        stats by up to n_prefill x). In event mode (``t`` given) a batch
        event is scheduled for the accepting node."""
        # prefix affinity first (a node holding the request's prefix
        # KVCache hot serves it suffix-only), then least SSE connections
        for p in sorted(self.prefills,
                        key=lambda x: (-x.prefix_affinity(req),
                                       x.sse_connections)):
            if p.draining or p.crashed or p.ejected:
                continue   # logical removal: not a rejection
            if p.offer(req):
                self.accepted.append(req.rid)
                self.n_accepted += 1
                if t is not None:
                    self._schedule_batch(p, max(t, p.busy_until))
                return True
            self.probe_rejections += 1
        self.rejections += 1
        return False

    def try_absorb(self, req: ServeRequest, t: float) -> bool:
        """Overload elasticity (DynaServe-style): when every prefill node
        rejected ``req``, an idle-capacity decode node can absorb it as
        CHUNKED prefill — the absorber engine (same params) runs
        ``prefix_align``-sized chunks between its decode steps, and the
        final chunk's stitched KV lands directly in the decode pool (no
        transfer). Token-identical to a monolithic prefill by the warm-
        continuation contracts (pinned per family in tests)."""
        if not self.absorb_prefill or t is None:
            return False
        if req.gw_attempts < 1:
            # second-chance rung: one backoff round-trip filters
            # transient prefill-full bursts — only sustained overload
            # spills prefill work onto the decode side
            return False
        total = len(req.tokens) + req.max_new_tokens + 1
        # a chunk's service wall (>= the per-batch base) dwarfs the TPOT
        # budget of co-resident decodes, so only a node with NO live
        # decode work may start absorbing
        cands = [d for d in self.decodes
                 if not (d.draining or d.crashed or d.ejected)
                 and d._absorb_job is None
                 and not d.requests
                 and not (self.sched and self.sched.pending_for(d.iid))
                 and self._free_capacity(d) > 0]
        for d in sorted(cands, key=lambda d: (len(d.requests), d.iid)):
            eng = d.absorber()
            if not eng.supports_prefix_reuse:
                return False           # family serves cold-only: no chunks
            if d.pool.free_blocks < d.pool.blocks_for_tokens(total):
                continue
            d.pool.alloc(req.rid, total)   # reserve prompt + gen room NOW
            cuts = eng.chunk_bounds(len(req.tokens),
                                    self.absorb_chunk_tokens)
            job = AbsorbJob(
                req=req, node=d,
                chunks=eng.iter_chunks(
                    req.tokens, chunk_tokens=self.absorb_chunk_tokens,
                    frames=req.frames),
                n_left=len(cuts) + 1)
            d._absorb_job = job
            self.accepted.append(req.rid)
            self.n_accepted += 1
            self.absorbs["absorb_requests"] += 1
            self.schedule(max(t, d.busy_until), "absorb", job)
            return True
        return False

    # ------------------------------------- transfer-pipeline callbacks
    def _free_capacity(self, d: DecodeNode) -> int:
        """Decode slots not yet spoken for: free minus in-flight transfer
        jobs minus an active absorbed prefill (its final chunk admits in
        place, so it holds one slot claim from the moment it starts)."""
        pend = self.sched.pending_for(d.iid) if self.sched else 0
        absorb = 1 if d._absorb_job is not None else 0
        return d.free_slot_count() - pend - absorb

    def _pick_decode(self, exclude: Tuple[DecodeNode, ...] = ()
                     ) -> Optional[DecodeNode]:
        cands = [d for d in self.decodes
                 if d not in exclude and d.can_admit()
                 and self._free_capacity(d) > 0
                 and not (self.sched
                          and d.iid in self.sched.failed_nodes)]
        return min(cands,
                   key=lambda d: len(d.requests)
                   + (self.sched.pending_for(d.iid) if self.sched else 0),
                   default=None)

    def _sched_pick(self, job: TransferJob) -> Optional[DecodeNode]:
        """Fallback target for a requeued job: prefer ANOTHER node than
        the one that drained/failed/conflicted; same node only if it is
        healthy and the sole candidate."""
        tgt = self._pick_decode(exclude=(job.dst,))
        return tgt if tgt is not None else self._pick_decode()

    def _on_admit(self, job: TransferJob):
        with trace.span("pd.xfer.admit"):
            job.dst.finish_admit(job.req, job.out)
            if job.req.wall_admit < 0.0:
                job.req.wall_admit = time.perf_counter()
            self.gen_tokens.append(job.req.max_new_tokens)
            if self._tickless:
                self._schedule_step(job.dst,
                                    max(job.admitted_t, job.dst.busy_until))

    # ------------------------------------------------------- event core
    def schedule(self, t: float, kind: str, obj: object = None):
        heapq.heappush(self.events, (t, next(self._eseq), kind, obj))

    def _schedule_batch(self, p: PrefillNode, t: float):
        if p._batch_evt:
            return
        p._batch_evt = True
        self.schedule(t, "batch", p)

    def _schedule_step(self, d: DecodeNode, t: float):
        if d._step_evt:
            return
        d._step_evt = True
        self.schedule(t, "step", d)

    def next_time(self) -> Optional[float]:
        """Earliest pending event on this group's timeline (queued group
        events and transfer-link landings)."""
        t = self.events[0][0] if self.events else None
        if self.sched is not None and not self.sched.idle():
            ts = self.sched.next_event()
            if ts is not None and (t is None or ts < t):
                t = ts
        return t

    def advance(self, until: float):
        """Drain group events and link-segment landings in global
        nondecreasing virtual-time order, up to and including ``until``.
        This is the tickless hot loop; the staged shim reuses the same
        handlers through _drain_queued."""
        for _ in range(1_000_000):
            t_ev = self.events[0][0] if self.events else None
            t_sc = None
            if self.sched is not None and not self.sched.idle():
                t_sc = self.sched.next_event()
            if t_sc is not None and t_sc <= until \
                    and (t_ev is None or t_sc <= t_ev):
                self.vclock = max(self.vclock, t_sc)
                self.event_log.append((t_sc, "segment"))
                self._pump(t_sc)
            elif t_ev is not None and t_ev <= until:
                t, _, kind, obj = heapq.heappop(self.events)
                if self.sched is not None:
                    self._pump(t)
                self.vclock = max(self.vclock, t)
                self.event_log.append((t, kind))
                self._dispatch(kind, t, obj)
            else:
                return
        raise RuntimeError(f"event loop runaway in group {self.gid}")

    def _drain_queued(self):
        """Pop every queued group event in time order (staged shim:
        events never outrun the handlers that scheduled them), pumping
        the transfer scheduler in lockstep so segment landings and
        admissions interleave at their true times."""
        while self.events:
            t, _, kind, obj = heapq.heappop(self.events)
            if self.sched is not None:
                self._pump(t)
            self.vclock = max(self.vclock, t)
            self.event_log.append((t, kind))
            self._dispatch(kind, t, obj)

    def _pump(self, t: float):
        with trace.span("pd.xfer.pump"):
            self.sched.pump(t)

    def _dispatch(self, kind: str, t: float, obj: object):
        if kind == "batch":
            self._ev_batch(t, obj)
        elif kind == "xfer":
            self._ev_xfer(t, obj)
        elif kind == "step":
            self._ev_step(t, obj)
        elif kind == "absorb":
            self._ev_absorb(t, obj)
        elif kind == "scale":
            if self.scaler is not None:
                self.scaler.on_event(t, self, obj)
        elif kind in ("fault", "hb", "eject", "requeue", "recover"):
            if self.ft is not None:
                self.ft.dispatch(kind, t, obj)
        # "pump": the pre-dispatch pump already retried waiting jobs;
        # "segment" is a ledger-only kind

    # ------------------------------------------------------- handlers
    def _ev_batch(self, t: float, p: PrefillNode):
        """Run a prefill node's formed batch at virtual time ``t``; the
        node is busy until t + measured wall seconds, TTFT ends (first
        token streams) at batch completion, and the transfer hand-off is
        scheduled there."""
        p._batch_evt = False
        if not p.forming:
            return
        if p.busy_until > t + 1e-12:       # mid-batch: wait for the node
            self._schedule_batch(p, p.busy_until)
            return
        batch_rids = [r.rid for r in p.forming]
        batch_tokens = sum(len(r.tokens) for r in p.forming)
        with trace.span("pd.prefill.batch"):
            t0 = time.perf_counter()
            ready = p.run_batch(collect_layers=self.overlap_transfer)
            t1 = time.perf_counter()
        w = t1 - t0
        if self.service_model is not None:
            # deterministic chaos runs: charge the model's virtual cost,
            # not the jittery measured wall time
            w = self.service_model.prefill_batch_s(batch_tokens)
        # heterogeneous node classes: the class scales the VIRTUAL
        # service time only (token streams are class-invariant)
        w *= p.prefill_scale
        self.prefill_batch_s.append(w)
        done = t + w
        p.busy_until = done
        self.vclock = max(self.vclock, done)
        if self.sched is not None:       # only consumer of the meta
            for rid in batch_rids:
                p.batch_meta[rid] = (t, w)
        for req, _ in ready:
            # a crash-displaced re-admit keeps its ORIGINAL first-token
            # stamp: TTFT ended when the first prefill streamed it
            if req.first_token_t < 0.0:
                req.first_token_t = done
                req.wall_first_token = t1
                if req.submit_t >= 0.0:
                    self.ttft_s.append(max(0.0, done - req.submit_t))
        # overlapped: the engine streams layers DURING the compute
        # window, so the hand-off (scheduler begin) is stamped at batch
        # start and segments land under the window (Fig. 10); blocking
        # transfer can only move the final KV at batch completion
        self.schedule(t if self.sched is not None else done, "xfer", p)
        if self.on_capacity is not None:   # forming slots freed
            self.on_capacity(done)
        self._trim_hists()

    def _ev_xfer(self, t: float, p: PrefillNode):
        """Hand prefilled requests to decode: pipelined transfer begin
        (overlapped) or inline blocking admission charging the D2D stall
        — including the recurrent-state payload of attn-free/SSM
        requests, whose ``out.k is None`` previously ledgered a free
        transfer."""
        if not p.waiting:
            return
        for pair in [pr for pr in p.waiting
                     if len(pr[0].generated) >= pr[0].max_new_tokens + 1]:
            # budget exhausted at prefill (max_new=0 scoring-style
            # requests): nothing to decode, so nothing to transfer —
            # finish where the first token streamed
            req, _ = pair
            p.waiting.remove(pair)
            req.done = True
            req.finish_t = max(t, req.first_token_t)
            p.pool.release(req.rid)
            p.batch_meta.pop(req.rid, None)
            p.staged.pop(req.rid, None)
            self.gen_tokens.append(req.max_new_tokens)
        remaining = []
        moved = False
        for req, out in p.waiting:
            tgt = self._pick_decode()
            if tgt is None:
                remaining.append((req, out))
                continue
            if self.sched is not None:
                t0v, w = p.batch_meta.pop(req.rid, (t, 0.0))
                self.sched.begin(
                    req, out, src_iid=p.iid, dst=tgt, t_start=t0v,
                    compute_s=w, payloads=p.staged.pop(req.rid, None),
                    fracs=p.engine.layer_fractions() or None,
                    on_admit=self._on_admit)
                p.pool.release(req.rid)
            else:
                tgt.admit(req, out, p.pool, self.xfer,
                          mode=self.transfer_mode)
                if req.wall_admit < 0.0:
                    req.wall_admit = time.perf_counter()
                stall = self.xfer.stats[-1].time_s if out.k is not None \
                    else 0.0
                state_b = state_payload_nbytes(out)
                if state_b:
                    # the mamba state / cross KV crosses the same link:
                    # state-only payloads pay wire time too
                    stall += self.xfer.link.time(state_b, 1)
                self.blocking_waits.append(stall)
                self.n_blocking_admits += 1
                start = max(t, self._blk_free_t)
                admitted = start + stall
                self._blk_free_t = admitted
                self.vclock = max(self.vclock, admitted)
                self.gen_tokens.append(req.max_new_tokens)
                if self._tickless:
                    self._schedule_step(tgt, max(admitted, tgt.busy_until))
            p.sse_connections -= 1
            moved = True
        p.waiting = remaining
        if moved and self.on_capacity is not None:
            self.on_capacity(t)

    def _ev_step(self, t: float, d: DecodeNode):
        """One decode iteration at virtual time ``t``; in tickless mode
        the node self-reschedules while it has requests, and completions
        retry the transfer hand-off (freed slots) at once."""
        d._step_evt = False
        if not d.requests:
            return
        if d.busy_until > t + 1e-12:
            self._schedule_step(d, d.busy_until)
            return
        n_slots = len(d.requests)
        with trace.span("pd.decode.step"):
            t0 = time.perf_counter()
            finished = d.step()
            w = time.perf_counter() - t0
        if self.service_model is not None:
            w = self.service_model.decode_step_s(n_slots)
        w *= d.decode_scale
        self.decode_step_s.append(w)
        done = t + w
        d.busy_until = done
        self.vclock = max(self.vclock, done)
        for req in finished:
            req.finish_t = done
        if self._tickless:
            if d.requests:
                self._schedule_step(d, done)
            if finished:
                for p in self.prefills:
                    if p.waiting:
                        self.schedule(done, "xfer", p)
                if self.sched is not None and not self.sched.idle():
                    self.schedule(done, "pump", None)
        self._trim_hists()

    def _ev_absorb(self, t: float, job: AbsorbJob):
        """Run ONE chunk of an absorbed prefill on its decode node at
        virtual time ``t``: the chunk charges the node's busy window
        (scaled by its class's prefill cost), so decode steps and
        further chunks interleave on the heap. The final chunk writes
        the full stitched KV into the node's own pool and admits the
        request in place — TTFT ends here."""
        d = job.node
        req = job.req
        if job.dead:
            return                      # crash evacuation re-offered it
        if d.crashed or d.ejected:
            # no fault controller claimed the job (ft-less run): requeue
            # through the gateway's displaced hook
            job.dead = True
            d._absorb_job = None
            d.pool.release(req.rid)
            self.absorbs["absorb_displaced"] += 1
            if self.on_displaced is not None:
                self.on_displaced(req, t)
            elif not self.offer(req, t=t):
                pass                    # dropped back to caller's ledger
            return
        if d.busy_until > t + 1e-12:
            self.schedule(d.busy_until, "absorb", job)
            return
        if job.n_left == 1 and not d.engine.free_slots() \
                and req.max_new_tokens >= 1:
            # the last chunk ends in an in-place admit, and decode
            # traffic filled every slot since the job started: hold the
            # final chunk until a step retires a request (poll — the
            # reserved pool blocks keep the admit itself safe)
            self.schedule(t + self.absorb_retry_s, "absorb", job)
            return
        t0 = time.perf_counter()
        n_chunk, out = next(job.chunks)
        t1 = time.perf_counter()
        w = t1 - t0
        if self.service_model is not None:
            w = self.service_model.prefill_batch_s(n_chunk)
        w *= d.prefill_scale            # decode iron runs prefill slower
        done = t + w
        d.busy_until = done
        self.vclock = max(self.vclock, done)
        job.out = out
        job.n_left -= 1
        self.absorbs["absorb_chunks"] += 1
        self.absorbs["absorb_tokens"] += int(n_chunk)
        if job.n_left > 0:
            self.schedule(done, "absorb", job)
            return
        # final chunk: KV home, admit in place, first token streams
        bs = d.pool.block_size
        if out.k is not None:
            d.pool.write_prefill(
                d.pool.owned(req.rid)[: (out.prompt_len + bs - 1) // bs],
                out.k, out.v)
        if req.first_token_t < 0.0:
            req.first_token_t = done
            req.wall_first_token = t1
            if req.submit_t >= 0.0:
                self.ttft_s.append(max(0.0, done - req.submit_t))
        req.generated.append(out.first_token)
        if req.on_token:
            req.on_token(out.first_token)
        self.gen_tokens.append(req.max_new_tokens)
        d._absorb_job = None
        if len(req.generated) >= req.max_new_tokens + 1:
            # prefill-complete budget: nothing to decode — finish in
            # place, the reserved blocks free without touching a slot
            req.done = True
            req.finish_t = done
            d.pool.release(req.rid)
            self._trim_hists()
            return
        d.finish_admit(req, out)
        if req.wall_admit < 0.0:
            req.wall_admit = time.perf_counter()
        if self._tickless:
            self._schedule_step(d, done)
        self._trim_hists()

    def _trim_hists(self):
        for hist in (self.prefill_batch_s, self.decode_step_s,
                     self.gen_tokens, self.ttft_s, self.accepted,
                     self.blocking_waits):
            if len(hist) > 512:
                del hist[:-256]
        if len(self.event_log) > 4096:
            del self.event_log[:-2048]

    # ------------------------------------------ staged compatibility shim
    def tick(self, tick_no: int):
        """Legacy staged step, now a shim over the event core: enqueue
        batch/transfer events at the current frontier, drain them (with
        the scheduler pumped in lockstep), take ONE decode iteration per
        busy node, then — replacing the old spinning-ticks hack — jump
        the frontier to the next pending event if nothing advanced."""
        if self.ft is not None:
            # _drain_queued pops queued events regardless of time, so a
            # future-dated fault/heartbeat would fire early and corrupt
            # the deterministic chaos timeline
            raise RuntimeError(
                "fault injection requires the tickless event loop; the "
                "staged tick() shim cannot honor future-dated fault "
                "events")
        self._tickless = False
        vt0 = self.vclock
        for p in self.prefills:
            if p.forming:
                self._schedule_batch(p, max(self.vclock, p.busy_until))
            elif p.waiting:
                self.schedule(self.vclock, "xfer", p)
        self._drain_queued()
        # completed last layers fire decode admission
        if self.sched is not None:
            self._pump(self.vclock)
        for d in self.decodes:
            if d.requests:
                self.event_log.append((self.vclock, "step"))
                self._ev_step(self.vclock, d)
        # event-frontier progress guarantee: transfers still in flight
        # with the group otherwise idle advance to the next link event
        # instead of spinning ticks
        if self.vclock <= vt0:
            nxt = self.next_time()
            if nxt is not None:
                self.advance(nxt)
        self._trim_hists()
        self._complete_flips(tick_no)

    # --------------------------------- runtime role flips (§3.3 on real)
    def draining_nodes(self) -> List[str]:
        return [n.iid for n in self.prefills + self.decodes if n.draining]

    def request_flip(self, src_role: str, *, min_each: int = 1
                     ) -> Optional[str]:
        """Mark the least-loaded node of `src_role` as draining; the swap
        itself happens in _complete_flips once its in-flight work is
        done. Returns the draining iid, or None if the group cannot give
        up a node (min_each single-point-failure floor)."""
        if src_role == "P":
            live = [p for p in self.prefills
                    if not (p.draining or p.crashed or p.ejected)]
            if len(live) <= min_each:
                return None
            node = min(live, key=lambda p: (len(p.forming) + len(p.waiting),
                                            p.iid))
        else:
            live = [d for d in self.decodes
                    if not (d.draining or d.crashed or d.ejected)]
            if len(live) <= min_each:
                return None
            node = min(live, key=lambda d: (len(d.requests), d.iid))
        node.draining = True
        return node.iid

    def _complete_flips(self, t: float):
        """``t``: tick number under the staged shim, virtual seconds in
        event mode (flip completion is itself a timestamped event)."""
        tf = float(t)
        flipped = False
        # decommissioning nodes drain OUT of the group (autoscale), not
        # into the opposite role — the scaler's re-check owns them
        for p in [x for x in self.prefills
                  if x.draining and not x.decommissioning]:
            if p.forming or p.waiting:
                continue   # in-flight prefill work must complete first
            self.prefills.remove(p)
            self.meta.remove_instance(tf, p.iid)
            d = self._new_decode(tf)
            d.node_class = p.node_class        # same iron, new role
            d.prefill_scale = p.prefill_scale
            d.decode_scale = p.decode_scale
            self.flips.append((t, p.iid, d.iid, "P->D"))
            self.n_flips += 1
            self.decodes.append(d)
            flipped = True
        for d in [x for x in self.decodes
                  if x.draining and not x.decommissioning]:
            if d.requests or d._absorb_job is not None \
                    or (self.sched is not None
                        and self.sched.pending_for(d.iid)):
                continue   # in-flight decodes/transfers must clear first
            self.decodes.remove(d)
            self.meta.remove_instance(tf, d.iid)
            p = self._new_prefill(tf)
            p.node_class = d.node_class
            p.prefill_scale = d.prefill_scale
            p.decode_scale = d.decode_scale
            self.flips.append((t, d.iid, p.iid, "D->P"))
            self.n_flips += 1
            self.prefills.append(p)
            flipped = True
        if len(self.flips) > 512:
            del self.flips[:-256]
        if flipped:
            self.event_log.append((tf, "flip"))
            if self._tickless:
                # fresh capacity: retry queued hand-offs and stranded jobs
                for p in self.prefills:
                    if p.waiting:
                        self.schedule(tf, "xfer", p)
                if self.sched is not None and not self.sched.idle():
                    self.schedule(tf, "pump", None)
            if self.on_capacity is not None:
                self.on_capacity(tf)

    # ------------------------------------------------------------- stats
    def observed_profile(self, *, min_samples: int = 3
                         ) -> Optional[InstanceProfile]:
        """InstanceProfile from this group's own measured timings, for
        Eq.1 when no profiled-in-advance numbers are supplied."""
        if (len(self.prefill_batch_s) < min_samples
                or len(self.decode_step_s) < min_samples):
            return None
        b_p = max(p.batch_size for p in self.prefills) if self.prefills \
            else 4
        b_d = max(d.engine.max_slots for d in self.decodes) if self.decodes \
            else 8
        # medians: first samples per shape carry one-time JIT compile
        # cost that would otherwise dominate the window
        return InstanceProfile(
            ttft_bs=max(_median(self.prefill_batch_s[-32:]), 1e-9), b_p=b_p,
            r_pre=1.0, tpot_bs=max(_median(self.decode_step_s[-32:]), 1e-9),
            b_d=b_d, gen_tokens=max(_mean(self.gen_tokens[-64:]), 1.0),
            xi=0.0)

    def prefix_stats(self) -> Dict[str, float]:
        """Aggregated prefix-reuse stats over this group's live prefill
        nodes (per-scenario index: routing affinity keeps a scenario's
        prefixes hot inside its own group, Fig. 1b)."""
        agg = {"lookups": 0.0, "hits": 0.0, "hit_tokens": 0.0,
               "evictions": 0.0, "cow_copies": 0.0,
               "compute_tokens": 0.0, "reused_tokens": 0.0,
               "snap_hits": 0.0, "snap_misses": 0.0,
               "snap_stores": 0.0, "snap_bytes": 0.0,
               "state_restores": 0.0}
        for p in self.prefills:
            for k, v in p.prefix_stats().items():
                agg[k] += v
        agg["hit_rate"] = agg["hits"] / agg["lookups"] if agg["lookups"] \
            else 0.0
        return agg

    def recent_admission_waits(self, n: int = 64) -> List[float]:
        """Tail of per-request admission waits (overlapped: scheduler
        ledger; blocking: D2D stalls) — the RatioAdjuster's
        decode-pressure signal."""
        if self.sched is not None:
            return list(self.sched.admission_waits[-n:])
        return list(self.blocking_waits[-n:])

    def transfer_stats(self) -> Dict[str, float]:
        """Per-group D2D pipeline stats: overlapped mode reports the
        scheduler's virtual-time ledger, blocking mode the synchronous
        stalls paid at the hand-off event. Both carry the group's
        MEASURED engine wall times (the same numbers the vclock
        charges), so the overlap pipeline's ready/busy arithmetic tracks
        the fused engines' real speed rather than a profiled guess.

        Prefill compile-stall telemetry rides along: the SHARED jitted
        prefill's live compile count (cluster-wide, O(num_buckets) under
        bucketing), the programs built inside prefill batches while
        tracing is on (cluster-wide, ``serving/trace.py``) and the
        pad-waste ratio (bucket-padding tokens over all tokens pushed
        through the forward)."""
        if self.sched is not None:
            out = dict(self.sched.stats())
            out["overlapped"] = 1.0
        else:
            w = self.blocking_waits
            out = {
                "overlapped": 0.0,
                "jobs_admitted": float(self.n_blocking_admits),
                "retries": 0.0, "requeues": 0.0,
                "admission_wait_mean_s": _mean(w),
                "link_busy_s": sum(w),
                "state_segments": 0.0, "state_payload_bytes": 0.0,
            }
        # medians: first samples per shape carry one-time JIT compile cost
        out["decode_step_median_s"] = _median(self.decode_step_s[-32:])
        out["prefill_batch_median_s"] = _median(self.prefill_batch_s[-32:])
        engines = [p.engine for p in self.prefills]
        batches = sum(e.prefill_batches for e in engines)
        comp = sum(e.compute_tokens for e in engines)
        padt = sum(e.padded_tokens for e in engines)
        out["prefill_compile_count"] = float(prefill_compile_count())
        out["prefill_batches"] = float(batches)
        out["prefill_builds"] = float(
            trace.builds().get("pd.prefill.batch", 0))
        out["prefill_pad_waste"] = padt / (comp + padt) \
            if comp + padt else 0.0
        for k, v in self.absorbs.items():   # chunked-prefill elasticity
            out[k] = float(v)
        if self.scaler is not None:         # autoscale ledger (scale_*)
            out.update(self.scaler.group_ledger(self.gid))
        if self.ft is not None:    # recovery ledger (serving/faults.py)
            out.update(self.ft.ledger())
        return out

    def stats(self) -> Dict[str, float]:
        n_p, n_d = self.ratio
        pf = self.prefix_stats()
        tf = self.transfer_stats()
        return {
            "n_p": n_p, "n_d": n_d,
            "accepted": self.n_accepted,
            "rejections": self.rejections,
            "probe_rejections": self.probe_rejections,
            "flips": self.n_flips,
            "ttft_s_mean": _mean(self.ttft_s),
            "prefix_hit_rate": pf["hit_rate"],
            "reused_tokens": pf["reused_tokens"],
            "transfer_overlapped": tf["overlapped"],
            "transfer_admission_wait_s": tf["admission_wait_mean_s"],
            "transfer_requeues": tf["requeues"],
        }


class RatioAdjuster:
    """Runtime P/D ratio adjustment for one ServeGroup (§3.3, Fig. 12).

    Every `interval` adjust steps: compute the Eq.1 optimum for the
    group's current node count from `profile` (profiled in advance) or
    from the group's observed timings, and flip ONE node toward it. When
    no profile is available yet, fall back to pure queue/TTFT pressure:
    gateway backlog + busy prefills + an idle decode means the prefill
    side is the bottleneck, and vice versa. A flip fires only after two
    consecutive adjust steps agree on the direction (hysteresis: noisy
    observed timings near the optimum must not ping-pong a node).
    Under the staged shim the adjust step IS the tick; the tickless
    frontend fires adjust steps every ``adjust_period_s`` virtual
    seconds instead.

    The per-group transfer pipeline's ADMISSION-WAIT ledger
    (ServeGroup.recent_admission_waits) weighs in alongside Eq.1 and the
    queue/TTFT pressure: prefilled KV waiting on a decode slot is decode
    starvation the TTFT-side signals cannot see, so a spike (recent
    waits >= wait_spike x the earlier window) votes P->D. An
    agreeing-or-unopposed vote shifts the suggestion; a vote that
    contradicts Eq.1 cancels the step, and after a wait-driven flip the
    opposite (D->P) correction is suppressed for ``wait_cooldown``
    adjust intervals — the relieved spike would otherwise expire
    immediately and Eq.1 would revert the flip every cycle, paying two
    node drains per round trip (conflicting evidence must not
    ping-pong nodes)."""

    def __init__(self, group: ServeGroup, *, interval: int = 8,
                 min_each: int = 1,
                 profile: Optional[InstanceProfile] = None,
                 wait_spike: float = 2.0, wait_min_s: float = 1e-5,
                 wait_cooldown: int = 4):
        self.group = group
        self.interval = max(1, interval)
        self.min_each = min_each
        self.profile = profile
        self.wait_spike = wait_spike
        self.wait_min_s = wait_min_s
        self.wait_cooldown = wait_cooldown
        self.decisions: List[Tuple[int, str]] = []
        self.wait_votes: List[int] = []    # ticks the wait signal fired
        self._last_want: Optional[str] = None
        self._wait_count = 0               # admissions seen at last eval
        self._wait_flip_tick: Optional[int] = None

    def _admission_wait_signal(self) -> Optional[str]:
        """P->D when the tail of admission waits spikes over the earlier
        window: segments are landing faster than decode frees slots.
        Only FRESH samples can vote — without new admissions since the
        last adjust tick the signal expires, so one historical burst
        cannot keep voting (or keep vetoing the corrective flip) on a
        quiet group."""
        g = self.group
        count = int(g.sched.n_admitted if g.sched is not None
                    else g.n_blocking_admits)
        fresh = count - self._wait_count
        self._wait_count = count
        if fresh <= 0:
            return None
        w = g.recent_admission_waits(64)
        if len(w) < 8:
            return None
        recent, base = _mean(w[-4:]), _mean(w[:-4])
        if recent >= self.wait_spike * max(base, self.wait_min_s):
            return "P->D"
        return None

    def maybe_adjust(self, tick_no: int, backlog: int = 0) -> Optional[str]:
        """`backlog`: gateway-queued requests homed to this group."""
        if tick_no == 0 or tick_no % self.interval:
            return None
        g = self.group
        if len(self.decisions) > 512:       # windowed retention
            del self.decisions[:-256]
        if len(self.wait_votes) > 512:
            del self.wait_votes[:-256]
        if g.scale_op is not None:
            # the autoscaler has a provision/decommission in flight:
            # stand down (hysteresis too — a half-confirmed flip must
            # not fire against the post-scale capacity)
            self._last_want = None
            return None
        if g.draining_nodes():
            return None   # one flip in flight at a time
        n_p, n_d = g.ratio
        total = n_p + n_d
        if total < 2 * self.min_each + 1:
            return None   # nothing to flip without violating min_each
        wait_want = self._admission_wait_signal()
        if wait_want is not None:
            self.wait_votes.append(tick_no)
        prof = self.profile or g.observed_profile()
        if prof is not None:
            # profile leads: at the Eq.1 optimum, only the admission-wait
            # vote (decode starvation Eq.1's medians lag behind) can
            # shift the suggestion; plain pressure fall-through here
            # would oscillate
            t_p, _ = optimal_ratio(prof, total, min_each=self.min_each)
            if t_p > n_p:
                want = "D->P"
            elif t_p < n_p:
                want = "P->D"
            else:
                want = wait_want
        else:
            want = self._pressure_signal(backlog) or wait_want
        wait_driven = want is not None and want == wait_want
        if want is not None and wait_want is not None and want != wait_want:
            want = None                   # conflicting evidence: stand down
        if (want == "D->P" and self._wait_flip_tick is not None
                and tick_no - self._wait_flip_tick
                < self.wait_cooldown * self.interval):
            want = None   # let the wait-driven extra decode prove itself
        if want is None:
            self._last_want = None
            return None
        if want != self._last_want:
            self._last_want = want        # needs confirmation next tick
            return None
        self._last_want = None
        if g.request_flip("D" if want == "D->P" else "P",
                          min_each=self.min_each) is None:
            return None
        if wait_driven:
            self._wait_flip_tick = tick_no
        self.decisions.append((tick_no, want))
        return want

    def _pressure_signal(self, backlog: int) -> Optional[str]:
        g = self.group
        tt = g.ttft_s
        ttft_rising = (len(tt) >= 16
                       and _mean(tt[-8:]) > 1.5 * _mean(tt[-16:-8]))
        prefill_busy = all(p.draining or not p.idle() for p in g.prefills)
        decode_idle = any(not d.draining and not d.requests
                          for d in g.decodes)
        if (backlog > 0 or ttft_rising) and prefill_busy and decode_idle:
            return "D->P"
        decode_full = all(not d.can_admit() for d in g.decodes)
        transfer_backlog = any(p.waiting for p in g.prefills)
        prefill_free = any(not p.draining and p.idle() for p in g.prefills)
        if decode_full and transfer_backlog and prefill_free:
            return "P->D"
        return None


class ClusterFrontend:
    """Gateway over N scenario groups on one shared virtual timeline
    (§3.2, §3.5).

    topology maps scenario tag -> (n_prefill, n_decode); groups are
    named g0, g1, ... in topology order. Requests route to their
    scenario's group first (unknown scenarios fall back to the
    least-loaded group) and forward across groups only when the home
    group rejects them everywhere.

    ``tickless=True`` (default): run() / serve() drain gateway arrivals
    and every group's event frontier in global virtual-time order —
    open-loop arrival schedules submit with ``submit(req, at=t)``.
    ``tickless=False`` restores the legacy synchronous tick loop (the
    per-group staged shim); both are token-identical by test."""

    def __init__(self, cfg: ModelConfig, *,
                 topology: Optional[Dict[str, Tuple[int, int]]] = None,
                 seed: int = 0, transfer_mode: str = "block_free",
                 params=None, link: Optional[LinkModel] = None,
                 adjust_ratio: bool = False, adjust_interval: int = 8,
                 min_each: int = 1,
                 profiles: Optional[Dict[str, InstanceProfile]] = None,
                 flat_iids: bool = False,
                 prefill_kwargs: Optional[dict] = None,
                 decode_kwargs: Optional[dict] = None,
                 prefix_cache: bool = True,
                 overlap_transfer: bool = True,
                 tickless: bool = True,
                 adjust_period_s: float = 0.25,
                 spec=None, faults=None,
                 fault_kwargs: Optional[dict] = None,
                 service_model=None,
                 health_timeout_s: Optional[float] = None,
                 absorb_prefill: bool = False,
                 absorb_chunk_tokens: int = 16,
                 queue_bound: Optional[int] = None,
                 gw_backoff_base_s: float = 0.005,
                 gw_backoff_cap_s: float = 0.16,
                 gw_max_attempts: int = 8):
        topology = topology or {"default": (1, 1)}
        if faults is not None and not tickless:
            raise ValueError("fault injection (faults=) requires "
                             "tickless=True: the staged tick loop cannot "
                             "honor future-dated fault events")
        prefill_kwargs = dict(prefill_kwargs or {})
        prefill_kwargs.setdefault("prefix_cache", prefix_cache)
        if flat_iids and len(topology) > 1:
            raise ValueError("flat_iids would collide instance ids across "
                             "groups; it is only for single-group shims")
        if params is None:
            params = init_params(cfg, jax.random.PRNGKey(seed))
        self.cfg = cfg
        self.params = params
        # per-store health timeout in VIRTUAL seconds (chaos runs use
        # sub-second timeouts; the 60 s default is wall-clock scale)
        self.meta = MetaStore() if health_timeout_s is None \
            else MetaStore(health_timeout_s=health_timeout_s)
        self.xfer = KVTransferEngine(link or LinkModel(), seed=seed)
        self.transfer_mode = transfer_mode
        self.tickless = bool(tickless)
        self.groups: Dict[str, ServeGroup] = {}
        self.adjusters: Dict[str, RatioAdjuster] = {}
        profiles = profiles or {}
        for i, (scenario, (n_p, n_d)) in enumerate(topology.items()):
            g = ServeGroup(
                f"g{i}", scenario, cfg, params, self.meta, self.xfer,
                n_prefill=n_p, n_decode=n_d, transfer_mode=transfer_mode,
                overlap_transfer=overlap_transfer,
                iid_prefix="" if flat_iids else None,
                prefill_kwargs=prefill_kwargs, decode_kwargs=decode_kwargs,
                spec=self._resolve_spec(spec, scenario, seed),
                fault_plan=(faults.get(scenario)
                            if isinstance(faults, dict) else faults),
                fault_kwargs=fault_kwargs, service_model=service_model,
                absorb_prefill=absorb_prefill,
                absorb_chunk_tokens=absorb_chunk_tokens)
            g.on_capacity = self._note_capacity
            g.on_displaced = self._gw_requeue
            self.groups[scenario] = g
            if adjust_ratio:
                self.adjusters[scenario] = RatioAdjuster(
                    g, interval=adjust_interval, min_each=min_each,
                    profile=profiles.get(scenario))
        self.pending: List[ServeRequest] = []
        self.tick_no = 0
        # ------------------------------------------ shared event timeline
        self.now = 0.0                      # gateway virtual-time frontier
        self.arrivals: List[Tuple[float, int, ServeRequest]] = []
        self._aseq = itertools.count()
        self._retry = False                 # capacity freed since last try
        self.adjust_period_s = float(adjust_period_s)
        self._next_adjust = self.adjust_period_s
        self._adjust_k = 0                  # synthetic adjust-step counter
        # ---------------------------------- gateway overload control
        # capped seeded backoff for timed arrivals no group will take
        # (mirrors the fault controller's requeue policy); SLO-aware:
        # ONLY past-deadline requests shed. Deadline-less requests park
        # in ``pending`` after the attempt cap and ride capacity events.
        self.queue_bound = queue_bound
        self.gw_backoff_base_s = float(gw_backoff_base_s)
        self.gw_backoff_cap_s = float(gw_backoff_cap_s)
        self.gw_max_attempts = int(gw_max_attempts)
        self._gw_rng = random.Random((seed << 8) ^ 0x5CA1E)
        self.gw_requeues = 0
        self.gw_sheds = 0
        self.gw_backpressure = 0            # over-bound signals upstream
        # ------------------------------------------------- autoscaler
        self.autoscaler = None              # attached by AutoScaler()
        self._next_autoscale = 0.0

    def attach_autoscaler(self, scaler):
        self.autoscaler = scaler
        for g in self.groups.values():
            g.scaler = scaler
        self._next_autoscale = scaler.period_s

    def _resolve_spec(self, spec, scenario: str, seed: int):
        """Scenario-aware draft binding for ``spec=``:

        * ``None`` — plain greedy decode (default);
        * a ``SpecConfig`` — one draft for every group;
        * ``"auto"`` — per-scenario ``draft_for`` pick (a small family
          drafting for the large one, speculation depth from the
          scenario's output-length profile);
        * a dict ``{scenario: SpecConfig | "auto" | None}`` — mixed
          fleets (e.g. speculate only on the long-generation group).
        """
        if spec is None:
            return None
        if isinstance(spec, dict):
            spec = spec.get(scenario)
            if spec is None:
                return None
        if spec == "auto":
            from repro.serving.speculative import draft_for
            return draft_for(self.cfg, scenario, seed=seed)
        return spec

    @property
    def rejections(self) -> int:
        return sum(g.rejections for g in self.groups.values())

    def group_for(self, req: ServeRequest) -> ServeGroup:
        sc = getattr(req, "scenario", "default")
        g = self.groups.get(sc)
        if g is not None:
            return g
        # unknown scenario: least-loaded group (a burst must not pile
        # onto g0 while other groups idle)
        return min(self.groups.values(), key=lambda x: (x.load(), x.gid))

    # ---------------------------------------------------------- ingress
    def submit(self, req: ServeRequest, *, at: Optional[float] = None):
        """Hand a request to the gateway. ``at`` (virtual seconds)
        enqueues a timed open-loop arrival on the event timeline;
        without it the request arrives "now" (the legacy synchronous
        path stamps the home group's frontier)."""
        if at is not None:
            req.submit_t = at
            heapq.heappush(self.arrivals, (at, next(self._aseq), req))
            return
        req.submit_t = self.now if self.tickless \
            else self.group_for(req).vclock
        self.pending.append(req)

    def _try_place(self, req: ServeRequest, t: Optional[float]) -> bool:
        """On-demand forwarding within the home group, then cross-group
        fallback (§3.5); under overload, chunked-prefill absorption on an
        idle-capacity decode node is the last resort before the request
        waits at the gateway (degradation order: absorb before
        backpressure)."""
        home = self.group_for(req)
        if home.offer(req, t=t):
            return True
        for g in self.groups.values():
            if g is not home and g.offer(req, t=t):
                return True
        if t is not None:
            if home.try_absorb(req, t):
                return True
            for g in self.groups.values():
                if g is not home and g.try_absorb(req, t):
                    return True
        return False

    def _note_capacity(self, t: float):
        self._retry = True

    def _retry_pending(self):
        self._retry = False
        still: List[ServeRequest] = []
        for req in self.pending:
            if not self._try_place(req, self.now):
                still.append(req)
        self.pending = still

    # --------------------------------------- overload control (gateway)
    def queued_backlog(self, scenario: Optional[str] = None) -> int:
        """Requests waiting at the gateway (timed backoff requeues plus
        parked pending) — the autoscaler's demand-pressure signal and
        the bounded-admission-queue measure."""
        n = 0
        for _, _, r in self.arrivals:
            if r.gw_attempts > 0 and (
                    scenario is None
                    or self.group_for(r).scenario == scenario):
                n += 1
        for r in self.pending:
            if scenario is None or self.group_for(r).scenario == scenario:
                n += 1
        return n

    def _gw_shed(self, req: ServeRequest, t: float):
        req.shed = True
        req.done = True
        req.finish_t = t
        self.gw_sheds += 1

    def _gw_requeue(self, req: ServeRequest, t: float):
        """A timed arrival no group (and no absorber) would take:
        capped, seeded exponential backoff mirroring the fault
        controller's requeue policy. SLO-aware degradation: a request
        already past its deadline sheds NOW (ledgered) — only
        past-deadline requests ever shed. Past the attempt cap a
        deadline-less request parks in ``pending`` (capacity events
        retry it) instead of spinning the event heap; one with a
        deadline schedules a single final wake-up at the deadline."""
        if req.slo_deadline_s >= 0.0 and req.submit_t >= 0.0 \
                and t >= req.submit_t + req.slo_deadline_s:
            self._gw_shed(req, t)
            return
        if self.queue_bound is not None \
                and self.queued_backlog() >= self.queue_bound:
            self.gw_backpressure += 1
        a = req.gw_attempts
        req.gw_attempts = a + 1
        if a >= self.gw_max_attempts:
            if req.slo_deadline_s < 0.0 or req.submit_t < 0.0:
                self.pending.append(req)
                return
            t_next = max(req.submit_t + req.slo_deadline_s,
                         t + self.gw_backoff_cap_s)
        else:
            delay = min(self.gw_backoff_base_s * (2.0 ** a),
                        self.gw_backoff_cap_s)
            t_next = t + delay * (1.0 + 0.1 * self._gw_rng.random())
        heapq.heappush(self.arrivals, (t_next, next(self._aseq), req))
        self.gw_requeues += 1

    # ------------------------------------------------- tickless event loop
    def serve(self, *, deadline: Optional[float] = None,
              watch: Optional[Sequence[ServeRequest]] = None,
              max_events: int = 1_000_000):
        """Drain the shared timeline — gateway arrivals, per-group
        batch/transfer/decode events and link-segment landings — in
        global nondecreasing virtual time. Stops at ``deadline`` (virtual
        seconds), when ``watch`` requests are all done, or when the
        timeline is empty."""
        for g in self.groups.values():
            g._tickless = True
        try:
            if self.pending:
                self._retry_pending()
            for _ in range(max_events):
                t_arr = self.arrivals[0][0] if self.arrivals else None
                t_grp, g_next = None, None
                for g in self.groups.values():
                    tg = g.next_time()
                    if tg is not None and (t_grp is None or tg < t_grp):
                        t_grp, g_next = tg, g
                if t_arr is None and t_grp is None:
                    break
                if t_arr is not None and (t_grp is None or t_arr <= t_grp):
                    if deadline is not None and t_arr > deadline:
                        break
                    _, _, req = heapq.heappop(self.arrivals)
                    self.now = max(self.now, t_arr)
                    if not (req.done or req.shed):
                        if req.gw_attempts == 0 \
                                and self.autoscaler is not None:
                            self.autoscaler.note_arrival(
                                self.group_for(req).scenario, t_arr,
                                gen_tokens=req.max_new_tokens)
                        with trace.span("pd.gateway.place"):
                            if not self._try_place(req, t_arr):
                                self._gw_requeue(req, t_arr)
                else:
                    if deadline is not None and t_grp > deadline:
                        break
                    self.now = max(self.now, t_grp)
                    g_next.advance(t_grp)
                    if g_next.draining_nodes():
                        g_next._complete_flips(g_next.vclock)
                if self._retry and self.pending:
                    self._retry_pending()
                if self.adjusters and self.now >= self._next_adjust:
                    self._run_adjusters()
                if self.autoscaler is not None \
                        and self.now >= self._next_autoscale:
                    self.autoscaler.step(self.now)
                    self._next_autoscale = \
                        self.now + self.autoscaler.period_s
                if watch is not None and all(r.done for r in watch):
                    break
        finally:
            for g in self.groups.values():
                g._tickless = False

    def _run_adjusters(self):
        """Periodic adjust step on the event timeline: every
        ``adjust_period_s`` virtual seconds, with a synthetic step
        counter in multiples of each adjuster's interval so the
        tick-modulo contract (and its hysteresis/cooldown arithmetic)
        carries over unchanged."""
        self._adjust_k += 1
        backlog: Dict[str, int] = {}
        for req in self.pending:
            sc = self.group_for(req).scenario
            backlog[sc] = backlog.get(sc, 0) + 1
        for sc, adj in self.adjusters.items():
            adj.maybe_adjust(self._adjust_k * adj.interval,
                             backlog.get(sc, 0))
        self._next_adjust = self.now + self.adjust_period_s

    # ----------------------------------------------- staged tick (shim)
    def tick(self):
        # 1. gateway: on-demand forwarding within the home group, then
        #    cross-group fallback (§3.5); unplaced requests wait here
        still: List[ServeRequest] = []
        for req in self.pending:
            if not self._try_place(req, None):
                still.append(req)
        self.pending = still
        # 2-4. per-group prefill / transfer / decode (+ drained flips)
        backlog: Dict[str, int] = {}
        for req in self.pending:
            sc = self.group_for(req).scenario
            backlog[sc] = backlog.get(sc, 0) + 1
        for g in self.groups.values():
            g.tick(self.tick_no)
        for sc, adj in self.adjusters.items():
            adj.maybe_adjust(self.tick_no, backlog.get(sc, 0))
        self.tick_no += 1
        self.now = max([self.now]
                       + [g.vclock for g in self.groups.values()])

    def run(self, requests: Sequence[ServeRequest], *,
            max_ticks: int = 200) -> List[ServeRequest]:
        if self.tickless:
            for r in requests:
                self.submit(r, at=self.now)
            self.serve(watch=list(requests))
            return list(requests)
        for r in requests:
            self.submit(r)
        for _ in range(max_ticks):
            self.tick()
            if all(r.done for r in requests):
                break
        return list(requests)

    def stats(self) -> Dict[str, Dict[str, float]]:
        return {sc: g.stats() for sc, g in self.groups.items()}

    def transfer_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-group transfer/overlap ledgers (Fig. 10 observability)."""
        return {sc: g.transfer_stats() for sc, g in self.groups.items()}

    def gateway_stats(self) -> Dict[str, float]:
        """Overload-control ledger: backoff requeues, SLO sheds,
        backpressure signals and the live gateway backlog."""
        return {
            "gw_requeues": float(self.gw_requeues),
            "gw_sheds": float(self.gw_sheds),
            "gw_backpressure": float(self.gw_backpressure),
            "gw_backlog": float(self.queued_backlog()),
        }
