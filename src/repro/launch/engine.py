"""Continuous-batching serving engine over the paged quantized KV cache.

The static serving path (`launch.serve.generate`) holds a (B, S_max)
cache: memory sized for the longest request, replicated per batch slot —
the software analogue of the FPnew lane replication TransDot removes in
hardware.  This engine removes it the same way: cache storage is a pool
of fixed-size pages (`core.kvcache` paged layout) shared by every live
request through per-request block tables, so cache memory scales with
live tokens, and one jit'd decode step serves a batch of requests at
*different* positions (per-request rope/mask via vector offsets).

Request lifecycle — admit -> prefill -> decode -> finish/evict:

  admit   : a waiting request is admitted when a decode slot is free and
            the `PageAllocator` can reserve ceil((prompt + max_new) /
            page) pages (full reservation, so a request never OOMs
            mid-decode; pages are reused off the free list).  With the
            prefix cache on (`EngineConfig.prefix_cache`), admission
            first matches the prompt against the radix index
            (`repro.serving.prefix_cache`): fully-matched pages are
            shared read-only into the block table (allocator refcounts
            keep them alive), a partial-page match copies-on-write into
            a private page, only the uncovered remainder allocates fresh
            pages, and cold cached prefixes LRU-evict under pool
            pressure.
  prefill : the prompt runs in fixed-size chunks against a contiguous
            (1, S_max) *staging* cache — the PR-2 quantized-cache path,
            unchanged — then the staged rows scatter into the request's
            pages (`write_prefill_rows`, pure relayout, bit-identical
            codes/scales).  The final chunk's logits yield the first
            generated token.  A prefix-hit request first materializes
            the matched rows from its (shared) pages into staging (pure
            relayout again) and prefills only from the divergence point
            — the skipped chunks are the `prefill_tokens_saved` the
            report counts; outputs stay bit-identical to a cold serve
            because the shared pages hold exactly the codes/scales a
            cold prefill of the same tokens would have written.  After
            the scatter, the request's pure full-prompt pages register
            in the prefix index for later requests to hit.
  decode  : all running requests step together through one fixed-shape
            jit'd call; each slot writes its token into its own page
            (`paged_write_token`) and attends through its block-table row
            via the `core.exec_plan` ``paged_decode`` route — the Pallas
            block-table kernel by default, with the `dpa_paged_decode_
            attn` jnp gather fallback pinned bit-identical.  Idle slots
            point at the scratch page and are ignored.
  finish  : on max_new (or eos) the request drops its page references;
            private pages return to the free list, shared prefix pages
            stay resident for future hits (the prefix cache holds its
            own reference), and the table row resets to scratch —
            eviction is page reuse, not memory churn.

The scheduler is token-budgeted: every step spends up to
`EngineConfig.token_budget` tokens — one per running decode request
first (decode latency is the serving SLO), the remainder on prefill
chunks of the oldest admitted request — so long prompts cannot starve
in-flight generations (chunked-prefill interleaving, the
Sarathi/DPUV4E-style scheduler-over-shared-engine structure).

Sampling: tokens draw through `repro.serving.sampler` — fixed-shape
temperature/top-k/top-p with per-request threefry streams keyed on
(seed, request id, token index), so a request's tokens are independent
of batch composition.  The default `SamplerConfig()` is greedy and
bit-identical to the argmax path this engine shipped with.

Speculative decoding (`SpecConfig`): the same weights draft k tokens
per request under a cheap low-precision policy, then ONE batched pass
under the serving policy verifies all k via the ``verify_attn`` route
and accepts with rejection sampling (`repro.serving.spec_decode`) —
greedy outputs stay token-for-token identical to plain decode.  Spec
mode commits pages lazily out of an up-front `PageAllocator`
reservation (the no-OOM guarantee survives) and rolls back pages
holding only rejected-draft rows after every round.  The token budget
prices a round at its real work: k draft + k+1 verify tokens per live
request.

Adaptive drafting (`repro.runtime.controller.ControllerConfig`): the
runtime analogue of the paper's mode register.  The engine pre-builds
one draft view per ladder rung at construction — every rung shares the
params and the page pool (`validate_policy_pair` against the serving
policy), each rung's ``paged_decode`` route resolved through the
exec-plan (tuned-DB consult included) — and a pure per-request feedback
controller demotes drafts toward fp4 while the acceptance EMA stays
high and promotes toward fp8/fp16 when it sags (hysteresis + dwell, no
flapping).  Each scheduler tick batches live requests *by current rung*
and runs one speculative round per rung group; requests on other rungs
ride the fixed-shape batch as ghosts (their stray writes land at rows
>= pos — stale territory every round rewrites before reading — or on
the scratch page, never over committed history).  Page reservations
size against the ladder-wide max draft k, so a rung switch can never
violate the no-OOM invariant.  Rejection sampling makes the output
distribution invariant to which rung drafted; greedy adaptive output is
token-for-token the plain engine's (pinned by
`tests/test_adaptive_engine.py`, adversarial controllers included).

Numerics contract: every path reuses the PR-2 quantized-cache machinery
(same `quant_rows_grid` recipe, same dequant-in-prologue attention), and
paging is pure relayout, so per-request greedy outputs are bit-identical
to the static-batch `serve.generate` path (pinned by
`tests/test_engine.py`), speculative or not (`tests/test_spec_decode.py`).

Entry points: `Engine` (programmatic), `synthetic_workload` (open-loop
Poisson traffic), `python -m repro.launch.serve --engine` (CLI demo).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import exec_plan
from repro.core import kvcache as KV
from repro.core.packing import operand_nbytes
from repro.core.policy import get_policy
from repro.distributed import tp as TP
from repro.runtime import controller as CTRL
from repro.runtime.controller import ControllerConfig
from repro.serving import sampler as SMP
from repro.serving import spec_decode as SPD
from repro.serving.prefix_cache import PrefixCache, PrefixMatch
from repro.serving.sampler import SamplerConfig
from repro.serving.spec_decode import SpecConfig

WAITING, PREFILL, DECODE, FINISHED = "waiting", "prefill", "decode", "done"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine geometry + scheduler knobs.

    S_max per request = max_pages_per_req * page_size (the block-table
    width bounds a request's timeline, not the pool's memory)."""
    page_size: int = 16
    n_pages: int = 64            # pool capacity (page 0 is scratch)
    max_batch: int = 4           # concurrent decode slots
    max_pages_per_req: int = 8   # block-table width
    token_budget: int = 16       # tokens per scheduler step
    prefill_chunk: int = 8       # prompt tokens per prefill call
    eos_id: int = -1             # stop token (-1: run to max_new)
    prefix_cache: bool = False   # share prompt prefixes across requests
    # tensor-parallel width: shard the page pool across a (1, tp) "model"
    # mesh and serve through the `*_sharded` exec-plan routes (bit-
    # identical outputs; the wire carries format-width codes + scales).
    # tp beyond the visible devices is an error; page_size % tp != 0
    # (the within-page row dim is the sharded one) serves replicated,
    # and report() states the reason.
    tp: int = 1

    @property
    def s_max(self) -> int:
        return self.max_pages_per_req * self.page_size


@dataclasses.dataclass
class Request:
    """One serving request plus its lifecycle/accounting state."""
    rid: int
    prompt: np.ndarray           # (S0,) int32 token ids
    max_new: int
    arrival: float = 0.0         # seconds after engine start (open loop)
    # -- runtime state (engine-owned) --
    state: str = WAITING
    out_tokens: list = dataclasses.field(default_factory=list)
    pages: list = dataclasses.field(default_factory=list)
    reserved_left: int = 0       # reserved-but-uncommitted pages (spec mode)
    rung: int = 0                # current draft-ladder rung (adaptive mode)
    ctrl: object = None          # ControllerState (adaptive mode)
    slot: int = -1
    pos: int = 0                 # tokens written to the cache so far
    prefill_done: int = 0
    prefill_skip: int = 0        # prompt tokens covered by a prefix hit
    t_admit: float = 0.0
    t_first: float = 0.0         # first generated token (TTFT anchor)
    t_finish: float = 0.0

    @property
    def n_prompt(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def n_generated(self) -> int:
        return len(self.out_tokens)

    def tokens(self) -> np.ndarray:
        """prompt + generated, the static path's (S0 + max_new,) layout."""
        return np.concatenate([self.prompt,
                               np.asarray(self.out_tokens, np.int32)])


def synthetic_workload(n_requests: int, *, vocab: int, seed: int = 0,
                       rate: float = 0.0, prompt_range=(8, 32),
                       gen_range=(4, 16), shared_prefix: int = 0,
                       mixed: float = 0.0) -> List[Request]:
    """Open-loop synthetic traffic: Poisson arrivals (exponential
    inter-arrival at `rate` req/s; rate 0 = all arrive at t=0), prompt
    and output lengths uniform over the given inclusive ranges.

    `shared_prefix` > 0 prepends the same `shared_prefix` drawn tokens
    to every prompt — a system-prompt workload, the prefix cache's
    target shape (the default 0 leaves the RNG stream, and so existing
    workloads, untouched).

    `mixed` > 0 makes the traffic heterogeneous: each request is a
    long-prompt/long-gen class member with probability `mixed` — prompt
    length uniform over [2*hi, 4*hi] of `prompt_range`, gen likewise of
    `gen_range` — the shape the adaptive draft controller is for (long
    generations give the acceptance EMA time to move the rung).  Every
    long-class draw (the class coin, lengths, AND tokens) comes from a
    *forked* RNG stream keyed (seed, 1), so the default ``mixed=0``
    leaves the base stream — and every existing workload and
    seed-determinism pin — byte-identical."""
    rng = np.random.default_rng(seed)
    hetero = np.random.default_rng([seed, 1]) if mixed > 0 else None
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests)) \
        if rate > 0 else np.zeros(n_requests)
    prefix = (rng.integers(0, vocab, size=shared_prefix).astype(np.int32)
              if shared_prefix > 0 else None)
    reqs = []
    for i in range(n_requests):
        if hetero is not None and hetero.random() < mixed:
            s0 = int(hetero.integers(2 * prompt_range[1],
                                     4 * prompt_range[1] + 1))
            gen = int(hetero.integers(2 * gen_range[1],
                                      4 * gen_range[1] + 1))
            prompt = hetero.integers(0, vocab, size=s0).astype(np.int32)
        else:
            s0 = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
            gen = int(rng.integers(gen_range[0], gen_range[1] + 1))
            prompt = rng.integers(0, vocab, size=s0).astype(np.int32)
        if prefix is not None:
            prompt = np.concatenate([prefix, prompt])
        reqs.append(Request(rid=i, prompt=prompt, max_new=gen,
                            arrival=float(arrivals[i])))
    return reqs


def _attn_group_kinds(cfg):
    """(pattern, n_groups, tail) with the engine's support check."""
    from repro.models.transformer import family_pattern
    pattern = family_pattern(cfg)
    if set(pattern) != {"attn"}:
        raise ValueError(
            f"engine serves uniform-attention decoder stacks; {cfg.name} "
            f"has pattern {pattern} (sliding-window/recurrent blocks keep "
            "per-slot state the paged cache does not model)")
    n_groups, tail = divmod(cfg.n_layers, len(pattern))
    return pattern, n_groups, tail


@dataclasses.dataclass
class _Rung:
    """One pre-built draft view on the adaptive ladder: the rung's
    policy/model share the serving params and page pool; only the
    compute routing (and jit'd step functions) differ per rung."""
    name: str
    k: int
    pol: object                  # validated TransPrecisionPolicy
    model: object                # serving model rebuilt under the rung
    plan: dict                   # paged_decode route description
    verify_plan: dict            # verify_attn route at sq = k + 1
    draft_fn: object             # jit'd draft step (donates caches)
    accept_fn: object            # jit'd rejection-sampling acceptance


class Engine:
    """Continuous-batching engine bound to one model + params.

    `sampler` selects the token-draw rule (default: greedy argmax);
    `spec` turns on self-speculative decoding (draft under
    `spec.draft_policy`, verify under the model's own policy);
    `adaptive` replaces the single static draft policy with a
    `ControllerConfig` precision ladder walked per request by the
    acceptance-feedback controller (`repro.runtime.controller`)."""

    def __init__(self, model, params, ecfg: EngineConfig, *,
                 sampler: Optional[SamplerConfig] = None,
                 spec: Optional[SpecConfig] = None,
                 adaptive: Optional[ControllerConfig] = None):
        if spec is not None and adaptive is not None:
            raise ValueError("pass spec= (one static draft policy) or "
                             "adaptive= (a controller-walked ladder), "
                             "not both")
        cfg = model.cfg
        pol = get_policy(cfg.policy)
        # tensor parallelism: a (1, tp) host mesh whose "model" axis
        # shards the page pool's within-page row dim (cache_spec's kv
        # rule).  More devices than exist is an error; a page size the
        # mesh does not divide serves replicated (the sharded routes'
        # in_specs would reject a non-dividing dim) and says so.
        self.tp, self.tp_fallback, self._mesh = 1, "", None
        if ecfg.tp > 1:
            n_dev = len(jax.devices())
            if ecfg.tp > n_dev:
                raise ValueError(f"tp={ecfg.tp} exceeds the {n_dev} "
                                 "visible device(s)")
            if ecfg.page_size % ecfg.tp:
                self.tp_fallback = (f"page_size={ecfg.page_size} not "
                                    f"divisible by tp={ecfg.tp}; serving "
                                    "replicated")
            else:
                from repro.launch.mesh import make_host_mesh
                self._mesh = make_host_mesh(n_data=1, n_model=ecfg.tp)
                self.tp = ecfg.tp
                # every device of the mesh holds the weights (a no-op
                # when the caller already built them replicated)
                params = jax.device_put(params, jax.sharding.NamedSharding(
                    self._mesh, jax.sharding.PartitionSpec()))
        # the plan layer owns kernel selection: resolving the decode route
        # up front validates the policy (a raw-f32-cache policy has no
        # paged_decode route) and makes the report say which kernel runs
        self._plan_ctx = dict(batch=ecfg.max_batch,
                              page_size=ecfg.page_size,
                              max_pages=ecfg.max_pages_per_req,
                              kv_heads=cfg.n_kv_heads, hd=cfg.hd,
                              n_pages=ecfg.n_pages, n_devices=self.tp)
        try:
            self.plan = exec_plan.describe("paged_decode", pol,
                                           **self._plan_ctx)
        except exec_plan.PlanError as e:
            raise ValueError(
                f"policy {cfg.policy!r} keeps a raw f32 cache; the paged "
                "engine stores format-width codes — pick a fmt_kv preset "
                "(e.g. kv8_attn_f32 for f32 arithmetic over an fp8 cache)"
            ) from e
        # MoE configs serve through the grouped_matmul plan: resolving it
        # up front states which grouped kernel the expert contraction
        # runs (the decode-step dispatch shape: each batch row buffers
        # its single token into (B, E, C, d) with C = f(S=1))
        self.moe_plan, self._moe_ctx = None, None
        if cfg.is_moe:
            c = int(cfg.capacity_factor * cfg.top_k / cfg.n_experts) + 1
            self._moe_ctx = dict(w_dtype="float32", eq="becd,edf->becf",
                                 e=cfg.n_experts, m=ecfg.max_batch * c,
                                 k=cfg.d_model, n=cfg.d_ff)
            self.moe_plan = exec_plan.describe("grouped_matmul", pol,
                                               **self._moe_ctx)
        if ecfg.s_max % ecfg.prefill_chunk:
            # the last chunk's fixed-size window must stay inside the
            # staging cache (dynamic_update_slice clamps, which would
            # shift the write over real rows)
            raise ValueError(f"S_max ({ecfg.s_max}) must be a multiple of "
                             f"prefill_chunk ({ecfg.prefill_chunk})")
        _, self._n_groups, self._n_tail = _attn_group_kinds(cfg)
        self.model, self.params, self.ecfg = model, params, ecfg
        self.cfg, self.pol = cfg, pol
        self.sampler = sampler or SamplerConfig()
        self.spec = spec
        self.alloc = KV.PageAllocator(ecfg.n_pages)
        self._table = np.full((ecfg.max_batch, ecfg.max_pages_per_req),
                              KV.SCRATCH_PAGE, np.int32)
        self.caches = self._init_paged_caches()
        if self._mesh is not None:
            self.caches = self._shard_caches(self.caches)
        # staging cache for chunked prefill: the contiguous PR-2 layout.
        # NEVER sharded: prefill softmax must stay a single-device
        # reduction or chunked prefill loses bit-identity with tp=1
        self._staging = model.init_caches(1, ecfg.s_max)
        self._prefill_fn = jax.jit(model.decode_step)
        self._decode_fn = jax.jit(self._make_decode_step(),
                                  donate_argnums=(2,))
        if spec is not None:
            self.draft_pol = SPD.validate_policy_pair(spec.draft_policy,
                                                      pol)
            from repro.models import build_model
            self.draft_model = build_model(
                cfg.replace(policy=spec.draft_policy))
            self.draft_plan = exec_plan.describe("paged_decode",
                                                 self.draft_pol,
                                                 **self._plan_ctx)
            self.verify_plan = exec_plan.describe("verify_attn", pol,
                                                  sq=spec.k + 1,
                                                  **self._plan_ctx)
            self._draft_fn = jax.jit(
                SPD.make_draft_step(self.draft_model, self.sampler),
                donate_argnums=(2,))
            self._verify_fn = jax.jit(self.model.decode_step,
                                      donate_argnums=(2,))
            self._accept_fn = jax.jit(
                SPD.make_accept_fn(self.sampler, spec.k))
        self.adaptive = adaptive
        self.rungs: List[_Rung] = []
        if adaptive is not None:
            # one draft view per rung, all sharing params and page pool:
            # validate_policy_pair pins the shared-cache precondition,
            # and each rung's paged_decode route resolves through the
            # exec-plan (tuned-DB consult included) at construction, so
            # a bad ladder entry fails here, not mid-request
            from repro.models import build_model
            for name, rk in zip(adaptive.ladder, adaptive.rung_ks):
                rpol = SPD.validate_policy_pair(name, pol)
                rmodel = build_model(cfg.replace(policy=name))
                self.rungs.append(_Rung(
                    name=name, k=rk, pol=rpol, model=rmodel,
                    plan=exec_plan.describe("paged_decode", rpol,
                                            **self._plan_ctx),
                    verify_plan=exec_plan.describe("verify_attn", pol,
                                                   sq=rk + 1,
                                                   **self._plan_ctx),
                    draft_fn=jax.jit(SPD.make_draft_step(rmodel,
                                                         self.sampler),
                                     donate_argnums=(2,)),
                    accept_fn=jax.jit(SPD.make_accept_fn(self.sampler,
                                                         rk))))
            self._verify_fn = jax.jit(self.model.decode_step,
                                      donate_argnums=(2,))
            # overridable seam: tests install adversarial controllers
            # (e.g. switch-every-round) through this attribute
            self._ctrl_step = CTRL.step
        self.prefix = (PrefixCache(ecfg.page_size, self.alloc)
                       if ecfg.prefix_cache else None)
        self.slots: List[Optional[Request]] = [None] * ecfg.max_batch
        self.waiting: List[Request] = []
        self._tables_dirty = False
        self.finished: List[Request] = []
        self.peak_live_tokens = 0
        self.n_steps = 0
        self.spec_rounds = 0
        self.spec_request_rounds = 0
        self.drafted = 0
        self.drafts_accepted = 0
        self.spec_emitted = 0
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prefill_tokens_saved = 0
        self.cow_copies = 0
        self.rung_rounds = [0] * len(self.rungs)
        self.rung_drafted = [0] * len(self.rungs)
        self.rung_accepted = [0] * len(self.rungs)
        self.rung_emitted = [0] * len(self.rungs)
        self.rung_wall = [0.0] * len(self.rungs)
        self.ctrl_switches = 0
        self.ctrl_demotes = 0
        self.ctrl_promotes = 0

    def _make_decode_step(self):
        """The jit'd plain decode step: model step + per-request sampling
        (greedy configs reduce to the argmax this engine always ran)."""
        model, scfg = self.model, self.sampler

        def step(params, batch, caches, rids):
            logits, caches = model.decode_step(params, batch, caches)
            tok = SMP.sample_tokens(logits[:, -1], rids,
                                    batch["index"] + 1, scfg)
            return tok, caches

        return step

    @property
    def _spec_k(self) -> int:
        """Draft-window rows priced into reservations and the submit
        guard.  Adaptive mode prices the *ladder-wide max* k: a rung
        switch mid-request must never grow a request past what was
        reserved at admission (the no-OOM invariant survives any
        controller trajectory)."""
        if self.adaptive is not None:
            return self.adaptive.max_k
        return self.spec.k if self.spec is not None else 0

    # -- cache plumbing ----------------------------------------------------

    def _init_paged_caches(self):
        """Paged pools in the model's scanned-cache structure: every leaf
        gains a leading (n_groups,) dim; per-layer pools are independent
        but share the one block table (vLLM-style: a request's page ids
        index every layer's pool)."""
        e, cfg = self.ecfg, self.cfg
        one = dict(KV.init_paged_kv_cache(e.n_pages, e.page_size,
                                          cfg.n_kv_heads, cfg.hd,
                                          fmt=self.pol.fmt_kv,
                                          packed=self.pol.kv_packed),
                   block_table=jnp.asarray(self._table))
        g = jax.tree.map(
            lambda x: jnp.array(jnp.broadcast_to(
                x[None], (self._n_groups,) + x.shape)), one)
        tail = [jax.tree.map(jnp.array, one) for _ in range(self._n_tail)]
        return {"groups": {"p0": g}, "tail": tail}

    def _shard_caches(self, caches):
        """Lay the page pools out on the TP mesh: within-page rows on
        "model" (cache_spec's kv rule, 1/tp of the pool per device),
        block tables replicated."""
        from repro.distributed.sharding import cache_spec
        return jax.tree.map(jax.device_put, caches,
                            cache_spec(caches, self._mesh))

    def _tp_scope(self):
        """Context every jit'd step runs (and so traces) under: the
        active TP mesh the sharded exec-plan routes read back, and under
        which the kernel wrappers give each device its own copy of a
        Mosaic kernel (`kernels.ops._run`)."""
        return (TP.activate(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def _unshard_staging(self):
        """Pull the staging cache back to one uncommitted device buffer.
        Gathering prefix rows out of the sharded pool leaves staging
        sharded; prefill must stay a single-device reduction (the tp=1
        bit-identity anchor), and an *uncommitted* buffer keeps the later
        pool scatter free to colocate with the committed pool."""
        self._staging = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x)), self._staging)

    def _sync_tables(self):
        """Push the host block table into every layer's cache leaf."""
        t = jnp.asarray(self._table)
        g = self.caches["groups"]["p0"]
        g = dict(g, block_table=jnp.asarray(np.ascontiguousarray(
            np.broadcast_to(self._table[None],
                            (self._n_groups,) + self._table.shape))))
        tail = [dict(c, block_table=t) for c in self.caches["tail"]]
        self.caches = {"groups": {"p0": g}, "tail": tail}
        if self._mesh is not None:
            # tables replicated on the mesh, beside their pool shards
            self.caches = self._shard_caches(self.caches)

    def _scatter_staging_to_pages(self, req: Request):
        """Copy the staged prompt rows into the request's pages (pure
        relayout; see `core.kvcache.write_prefill_rows`).  A prefix-hit
        request scatters only from its divergence point on — rows before
        `prefill_skip` live in shared (or CoW-copied) pages that must
        not be written."""
        n, start = req.n_prompt, req.prefill_skip
        ids = req.pages

        def copy_group(pages, staged):
            rows = {k: staged[k][0] for k in KV.QUANT_KEYS}
            return KV.write_prefill_rows(pages, rows, ids, n, start=start)

        g = self.caches["groups"]["p0"]
        sg = self._staging["groups"]["p0"]
        g = jax.vmap(copy_group)({k: g[k] for k in KV.QUANT_KEYS},
                                 {k: sg[k] for k in KV.QUANT_KEYS})
        self.caches["groups"]["p0"] = dict(self.caches["groups"]["p0"], **g)
        for i, (pc, sc) in enumerate(zip(self.caches["tail"],
                                         self._staging["tail"])):
            rows = {k: sc[k][0] for k in KV.QUANT_KEYS}
            self.caches["tail"][i] = KV.write_prefill_rows(pc, rows, ids, n,
                                                           start=start)
        if self._mesh is not None:
            # eager scatter output sharding is compiler-chosen; pin the
            # pool back to its canonical mesh layout (pure relayout)
            self.caches = self._shard_caches(self.caches)

    def _cow_copy(self, src: int, dst: int, n_rows: int):
        """Copy the first `n_rows` rows of pool page `src` into the
        private page `dst`, every layer — pure relayout (codes and
        scales move bit-for-bit), so the diverging request's view of the
        partially-shared block is exactly what a cold prefill would have
        written there.  The shared source page is read, never written."""
        def copy_group(pool):
            return {k: pool[k].at[dst, :n_rows].set(pool[k][src, :n_rows])
                    for k in KV.QUANT_KEYS}

        g = self.caches["groups"]["p0"]
        g2 = jax.vmap(copy_group)({k: g[k] for k in KV.QUANT_KEYS})
        self.caches["groups"]["p0"] = dict(g, **g2)
        for i, pc in enumerate(self.caches["tail"]):
            self.caches["tail"][i] = dict(pc, **copy_group(pc))
        if self._mesh is not None:
            self.caches = self._shard_caches(self.caches)
        self.cow_copies += 1

    def _load_prefix_to_staging(self, req: Request):
        """Materialize the matched rows [0, prefill_skip) from the
        request's pages into the contiguous staging cache — the inverse
        relayout of `_scatter_staging_to_pages` — so the warm prefill's
        chunks attend over exactly the codes/scales a cold prefill of
        the same prompt would have staged (the bit-identity anchor)."""
        m, ps = req.prefill_skip, self.ecfg.page_size
        ids = np.asarray(req.pages[:-(-m // ps)], np.int32)

        def gather_group(pool, staged):
            out = {}
            for k in KV.QUANT_KEYS:
                rows = pool[k][ids].reshape((-1,) + pool[k].shape[2:])[:m]
                out[k] = staged[k].at[0, :m].set(rows)
            return out

        g = self.caches["groups"]["p0"]
        sg = self._staging["groups"]["p0"]
        new = jax.vmap(gather_group)({k: g[k] for k in KV.QUANT_KEYS},
                                     {k: sg[k] for k in KV.QUANT_KEYS})
        self._staging["groups"]["p0"] = dict(sg, **new)
        for i, (pc, sc) in enumerate(zip(self.caches["tail"],
                                         self._staging["tail"])):
            self._staging["tail"][i] = dict(sc, **gather_group(pc, sc))
        if self._mesh is not None:
            self._unshard_staging()

    # -- lifecycle ---------------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        """Pages a request may touch over its lifetime.  Spec mode adds
        the draft window: a round writes query rows up to pos + k, so
        the reservation prices prompt + max_new + k rows (admission
        accounts the speculation overhead up front — the no-OOM-
        mid-decode invariant is a reservation, never a hope)."""
        rows = req.n_prompt + req.max_new + self._spec_k
        return -(-rows // self.ecfg.page_size)

    def submit(self, req: Request):
        e = self.ecfg
        total = req.n_prompt + req.max_new + self._spec_k
        if total > e.s_max:
            raise ValueError(f"request {req.rid}: {total} tokens "
                             f"(incl. the {self._spec_k}-token draft "
                             f"window) exceed S_max = {e.s_max} "
                             "(raise max_pages_per_req or page_size)")
        if self._pages_needed(req) > self.alloc.capacity - 1:
            raise ValueError(f"request {req.rid} can never fit the pool")
        req.state = WAITING
        self.waiting.append(req)

    def _match_prefix(self, req: Request) -> Optional[PrefixMatch]:
        """Match-and-pin: look the prompt up in the prefix index, take a
        request reference on every matched page (the shared full pages
        AND the CoW source) *before* any eviction runs — a just-matched
        cache-only page sits at refcount 1 and must not be reclaimed
        between the match and this request's use of it — then LRU-evict
        cold cached prefixes to cover the allocation shortfall."""
        if self.prefix is None:
            return None
        e = self.ecfg
        # at least one prompt token must prefill (the final chunk's
        # logits yield the first generated token), and the warm start's
        # fixed chunk window must fit inside the staging cache
        limit = min(req.n_prompt - 1, e.s_max - e.prefill_chunk)
        m = self.prefix.match(req.prompt, limit)
        self.alloc.incref(m.pages)
        if m.cow is not None:
            self.alloc.incref([m.cow[0]])
        short = (self._pages_needed(req) - len(m.pages)
                 - self.alloc.n_available)
        if short > 0:
            self.prefix.evict(short)
        return m

    def _unpin_match(self, m: PrefixMatch):
        """Drop the references `_match_prefix` pinned (admission did not
        go through); the pages stay resident under the cache's own ref."""
        self.alloc.free(m.pages)
        if m.cow is not None:
            self.alloc.free([m.cow[0]])

    def _admit(self, now: float):
        for slot in range(self.ecfg.max_batch):
            if self.slots[slot] is not None or not self.waiting:
                continue
            req = self.waiting[0]
            n_pages = self._pages_needed(req)
            match = self._match_prefix(req)     # pins matched pages
            shared = list(match.pages) if match is not None else []
            fresh = n_pages - len(shared)
            if not self.alloc.can_alloc(fresh):
                if match is not None:
                    self._unpin_match(match)
                break                      # FIFO: don't starve the head
            self.waiting.pop(0)
            if self.spec is not None:
                # lazy commit: reserve the lifetime worst case, pop only
                # the prompt's pages now; rounds commit/roll back the rest
                n0 = -(-req.n_prompt // self.ecfg.page_size)
                self.alloc.reserve(fresh)
                req.pages = shared + self.alloc.alloc(n0 - len(shared),
                                                      reserved=True)
                req.reserved_left = fresh - (n0 - len(shared))
            else:
                req.pages = shared + self.alloc.alloc(fresh)
            if match is not None:
                # stats count admissions, not retries: a request that
                # waited several ticks for pages is still one query
                self.prefix_queries += 1
                req.prefill_skip = req.prefill_done = match.tokens
                self.prefix_hits += match.tokens > 0
                self.prefill_tokens_saved += match.tokens
                if match.cow is not None:
                    src, rows = match.cow
                    # copy now, while the source pin is held; afterwards
                    # the source's content no longer matters to us
                    self._cow_copy(src, req.pages[len(shared)], rows)
                    self.alloc.free([src])
            if self.adaptive is not None:
                req.rung = self.adaptive.start_rung
                req.ctrl = CTRL.init_state(self.adaptive)
            req.slot, req.state, req.t_admit = slot, PREFILL, now
            self.slots[slot] = req
            # the table row stays scratch until prefill lands: a PREFILL
            # slot rides decode steps as idle and must not touch its pages

    def _finish(self, req: Request, now: float):
        self.alloc.free(req.pages)
        req.pages = []
        if req.reserved_left:
            self.alloc.unreserve(req.reserved_left)
            req.reserved_left = 0
        self._table[req.slot] = KV.SCRATCH_PAGE
        self.slots[req.slot] = None
        req.slot = -1
        req.state, req.t_finish = FINISHED, now
        self.finished.append(req)
        self._tables_dirty = True

    def _commit_pages(self, req: Request, n_rows: int) -> bool:
        """Commit pages out of the request's reservation until its block
        table covers `n_rows` timeline rows.  Returns True when the host
        table changed (caller syncs before the next device step)."""
        need = -(-n_rows // self.ecfg.page_size) - len(req.pages)
        if need <= 0:
            return False
        if need > req.reserved_left:
            raise RuntimeError(
                f"request {req.rid}: {n_rows} rows need {need} more pages "
                f"but only {req.reserved_left} are reserved (reservation "
                "accounting bug)")
        for pid in self.alloc.alloc(need, reserved=True):
            self._table[req.slot, len(req.pages)] = pid
            req.pages.append(pid)
        req.reserved_left -= need
        return True

    def _rollback(self, req: Request, n_rows: int):
        """Free committed pages past the accepted timeline (`n_rows`
        valid rows) back into the request's reservation and point the
        truncated block-table tail at scratch.  Pages holding only
        rejected-draft rows return here; pages the accepted timeline
        still touches are kept (stale rows inside them are masked by
        position and overwritten by the next round's writes)."""
        keep = -(-n_rows // self.ecfg.page_size)
        drop = req.pages[keep:]
        if not drop:
            return
        self.alloc.free(drop, to_reserved=True)
        req.reserved_left += len(drop)
        req.pages = req.pages[:keep]
        self._table[req.slot, keep:] = KV.SCRATCH_PAGE
        self._tables_dirty = True

    def _prefill_step(self, req: Request, now: float) -> int:
        """Run one prompt chunk; returns real tokens consumed."""
        e = self.ecfg
        c0 = req.prefill_done
        if req.prefill_skip > 0 and c0 == req.prefill_skip:
            # first chunk of a prefix-hit request: pull the matched rows
            # out of its (shared/CoW) pages into staging, then prefill
            # only from the divergence point
            self._load_prefix_to_staging(req)
        n = min(e.prefill_chunk, req.n_prompt - c0)
        if c0 % e.prefill_chunk:
            # realign a warm start to the chunk grid with one short
            # chunk, so every later fixed-size window stays inside the
            # staging cache (S_max is a chunk multiple; chunk splits do
            # not change numerics — rows are quantized before attention)
            n = min(n, e.prefill_chunk - c0 % e.prefill_chunk)
        chunk = np.zeros((1, e.prefill_chunk), np.int32)
        chunk[0, :n] = req.prompt[c0:c0 + n]
        with self._tp_scope():
            logits, self._staging = self._prefill_fn(
                self.params, {"tokens": jnp.asarray(chunk),
                              "index": jnp.int32(c0)}, self._staging)
        req.prefill_done += n
        if req.prefill_done == req.n_prompt:
            self._scatter_staging_to_pages(req)
            self._table[req.slot, :len(req.pages)] = req.pages
            self._tables_dirty = True
            if self.prefix is not None:
                # only now do the pages hold the prompt's rows; register
                # the pure full-prompt blocks for later requests to hit
                self.prefix.insert(req.prompt, req.pages)
            # the first generated token sits at timeline index n_prompt;
            # greedy configs reduce to the original argmax bit-for-bit
            first = int(SMP.sample_tokens(
                logits[:, n - 1], jnp.asarray([req.rid], jnp.int32),
                jnp.asarray([req.n_prompt], jnp.int32), self.sampler)[0])
            req.out_tokens.append(first)
            req.pos = req.n_prompt
            req.state, req.t_first = DECODE, now
            self._maybe_finish(req, first, now)
        return n

    def _live_batch(self):
        """(live requests, tokens (B,1), positions (B,), rids (B,)) for
        one fixed-shape step; idle slots ride along pointing at scratch."""
        e = self.ecfg
        live = [r for r in self.slots if r is not None and r.state == DECODE]
        tokens = np.zeros((e.max_batch, 1), np.int32)
        positions = np.zeros((e.max_batch,), np.int32)
        rids = np.zeros((e.max_batch,), np.int32)
        for r in live:
            tokens[r.slot, 0] = r.out_tokens[-1]
            positions[r.slot] = r.pos
            rids[r.slot] = r.rid
        return live, tokens, positions, rids

    def _decode_batch(self, now: float) -> int:
        """One batched decode step over every DECODE-state slot."""
        live, tokens, positions, rids = self._live_batch()
        if not live:
            return 0
        with self._tp_scope():
            nxt, self.caches = self._decode_fn(
                self.params, {"tokens": jnp.asarray(tokens),
                              "index": jnp.asarray(positions)}, self.caches,
                jnp.asarray(rids))
        nxt = np.asarray(nxt)
        for r in live:
            tok = int(nxt[r.slot])
            r.pos += 1
            r.out_tokens.append(tok)
            self._maybe_finish(r, tok, now)
        return len(live)

    def _spec_round(self, now: float, live: List[Request], k: int,
                    draft_fn, accept_fn, rung_i: Optional[int] = None) -> int:
        """One speculative round over the `live` participants: k draft
        steps under the draft policy, one k+1-token verify pass under
        the serving policy, rejection-sampled acceptance, then paged-KV
        rollback of pages holding only rejected rows.  Returns the
        token-budget cost: the round really runs 2k+1 model tokens per
        participant (k draft + k+1 verify).

        `live` may be a *subset* of the DECODE slots (adaptive mode
        batches by rung).  The fixed-shape batch still carries every
        DECODE slot at its real (last token, position) — non-
        participants are ghost riders: their stray K/V writes land at
        rows >= pos (stale territory their own next round rewrites
        before any read) or on the scratch page (rows past their
        committed tables), never over committed history; their sampled
        draws burn no RNG state (stateless threefry keyed on (seed,
        rid, index)); and only participants' outputs are read back."""
        e = self.ecfg
        _, tokens, positions, rids = self._live_batch()
        # commit pages for the participants' draft window (rows pos ..
        # pos+k) and push the grown tables before anything reads them
        dirty = [self._commit_pages(r, r.pos + k + 1) for r in live]
        if any(dirty) or self._tables_dirty:
            self._sync_tables()
            self._tables_dirty = False
        toks = jnp.asarray(tokens)
        pos = jnp.asarray(positions)
        rid_arr = jnp.asarray(rids)
        cur, drafts, draft_probs = toks, [], []
        with self._tp_scope():
            for i in range(k):
                d, q, self.caches = draft_fn(
                    self.params, {"tokens": cur, "index": pos + i},
                    self.caches, rid_arr)
                drafts.append(d)
                draft_probs.append(q)
                cur = d[:, None]
            drafts = jnp.stack(drafts, axis=1)               # (B, k)
            logits, self.caches = self._verify_fn(
                self.params,
                {"tokens": jnp.concatenate([toks, drafts], axis=1),
                 "index": pos}, self.caches)
        emitted, acc = accept_fn(
            drafts, None if self.sampler.greedy
            else jnp.stack(draft_probs, axis=1), logits, rid_arr, pos)
        emitted, acc = np.asarray(emitted), np.asarray(acc)
        self.spec_rounds += 1
        self.spec_request_rounds += len(live)
        if rung_i is not None:
            self.rung_rounds[rung_i] += 1
        for r in live:
            a = int(acc[r.slot])
            self.drafted += k
            self.drafts_accepted += a
            emit = [int(emitted[r.slot, j])
                    for j in range(min(a + 1, r.max_new - r.n_generated))]
            for j, tok in enumerate(emit):
                if tok == e.eos_id:
                    emit = emit[:j + 1]
                    break
            r.out_tokens.extend(emit)
            r.pos += len(emit)
            self.spec_emitted += len(emit)
            if rung_i is not None:
                self.rung_drafted[rung_i] += k
                self.rung_accepted[rung_i] += a
                self.rung_emitted[rung_i] += len(emit)
            if r.n_generated >= r.max_new or emit[-1] == e.eos_id:
                self._finish(r, now)
            else:
                self._rollback(r, r.pos)
                if rung_i is not None:
                    # pure feedback update — no wall clock, no RNG; the
                    # seam is overridable so tests can drive adversarial
                    # (e.g. switch-every-round) trajectories
                    r.ctrl, nxt = self._ctrl_step(self.adaptive, r.ctrl,
                                                  a, k)
                    if nxt != r.rung:
                        self.ctrl_switches += 1
                        if nxt < r.rung:
                            self.ctrl_demotes += 1
                        else:
                            self.ctrl_promotes += 1
                        r.rung = nxt
        return len(live) * (2 * k + 1)

    def _spec_decode_batch(self, now: float) -> int:
        """One static-draft speculative round over every DECODE slot."""
        live = [r for r in self.slots if r is not None and r.state == DECODE]
        if not live:
            return 0
        return self._spec_round(now, live, self.spec.k, self._draft_fn,
                                self._accept_fn)

    def _spec_decode_all(self, now: float) -> int:
        """Adaptive tick: batch live requests by current rung, run one
        speculative round per non-empty rung group (groups snapshot up
        front — a request that switches rungs during its own round is
        not served twice in one tick)."""
        live = [r for r in self.slots if r is not None and r.state == DECODE]
        if not live:
            return 0
        groups = [[r for r in live if r.rung == i]
                  for i in range(len(self.rungs))]
        cost = 0
        for i, group in enumerate(groups):
            if not group:
                continue
            rg = self.rungs[i]
            t0 = time.monotonic()
            cost += self._spec_round(now, group, rg.k, rg.draft_fn,
                                     rg.accept_fn, rung_i=i)
            self.rung_wall[i] += time.monotonic() - t0
        return cost

    def _maybe_finish(self, req: Request, tok: int, now: float):
        if req.n_generated >= req.max_new or tok == self.ecfg.eos_id:
            self._finish(req, now)

    def step(self, now: float = 0.0):
        """One scheduler tick: admit, decode the running batch, spend the
        leftover token budget on prefill chunks."""
        self._admit(now)
        budget = self.ecfg.token_budget
        if self.adaptive is not None:
            budget -= self._spec_decode_all(now)
        elif self.spec is not None:
            budget -= self._spec_decode_batch(now)
        else:
            budget -= self._decode_batch(now)
        while budget > 0:
            pre = [r for r in self.slots
                   if r is not None and r.state == PREFILL]
            if not pre:
                break
            # a partially-prefilled request MUST keep the baton until its
            # prompt is fully staged: the staging cache is shared, so
            # switching mid-prefill would interleave two prompts' rows
            # (there is at most one partial request by induction; a
            # prefix-hit request starts at prefill_done == prefill_skip,
            # so "untouched" is done == skip, not done == 0).  Ties on
            # t_admit (same tick) then break by admission order (rid)
            budget -= self._prefill_step(
                min(pre, key=lambda r: (r.prefill_done == r.prefill_skip,
                                        r.t_admit, r.rid)), now)
        self._admit(now)        # freed slots/pages admit within the tick
        if self._tables_dirty:
            # one device sync per tick, after all finish/prefill events —
            # the next tick's decode reads tables through the cache pytree.
            # Deferring past _finish is safe: the freed slot's stale row
            # only matters to decode, which never runs before this sync
            self._sync_tables()
            self._tables_dirty = False
        self.peak_live_tokens = max(self.peak_live_tokens,
                                    self.live_tokens())
        self.n_steps += 1

    def live_tokens(self) -> int:
        return sum(r.pos for r in self.slots if r is not None)

    def reset_stats(self):
        """Clear accounting between workloads (keeps compiled steps, the
        page pool, AND any resident cached prefixes — a warm cache is
        the point; only legal when nothing is in flight)."""
        if any(self.slots) or self.waiting:
            raise RuntimeError("reset_stats with requests in flight")
        self.finished = []
        self.peak_live_tokens = 0
        self.n_steps = 0
        self.spec_rounds = 0
        self.spec_request_rounds = 0
        self.drafted = 0
        self.drafts_accepted = 0
        self.spec_emitted = 0
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prefill_tokens_saved = 0
        self.cow_copies = 0
        self.rung_rounds = [0] * len(self.rungs)
        self.rung_drafted = [0] * len(self.rungs)
        self.rung_accepted = [0] * len(self.rungs)
        self.rung_emitted = [0] * len(self.rungs)
        self.rung_wall = [0.0] * len(self.rungs)
        self.ctrl_switches = 0
        self.ctrl_demotes = 0
        self.ctrl_promotes = 0
        self.alloc.peak_in_use = self.alloc.in_use

    def run(self, requests: List[Request]) -> dict:
        """Serve an open-loop workload to completion; returns `report()`.

        Requests arrive at wall-clock `arrival` offsets; the engine idles
        (sleeps) when nothing is live and the next arrival is in the
        future."""
        pending = sorted(requests, key=lambda r: r.arrival)
        t0 = time.monotonic()
        while pending or self.waiting or any(self.slots):
            now = time.monotonic() - t0
            while pending and pending[0].arrival <= now:
                self.submit(pending.pop(0))
            if not self.waiting and not any(self.slots):
                time.sleep(min(0.001, max(0.0,
                                          pending[0].arrival - now)))
                continue
            self.step(now)
        wall = time.monotonic() - t0
        return self.report(wall)

    # -- accounting --------------------------------------------------------

    def kv_bytes_report(self) -> dict:
        """Cache bytes from *actual per-request lengths* (live or peak
        tokens), vs the static (B, S_max) baselines — both the f32 seed
        cache and the format-width static cache the engine replaces."""
        e, cfg, pol = self.ecfg, self.cfg, self.pol
        n_attn = self._n_groups + self._n_tail
        live = KV.paged_kv_cache_nbytes(
            self.peak_live_tokens, self.alloc.peak_in_use, e.page_size,
            cfg.n_kv_heads, cfg.hd, fmt=pol.fmt_kv, packed=pol.kv_packed)
        static = KV.kv_cache_nbytes(e.max_batch, e.s_max, cfg.n_kv_heads,
                                    cfg.hd, fmt=pol.fmt_kv,
                                    packed=pol.kv_packed)
        return {
            "live_bytes": live["live"] * n_attn,
            "paged_bytes": live["paged"] * n_attn,
            "static_bytes": static["total"] * n_attn,
            "static_f32_bytes": static["f32_total"] * n_attn,
            "peak_live_tokens": self.peak_live_tokens,
            "page_util": self.alloc.peak_in_use / (self.alloc.capacity - 1),
            "pages_peak": self.alloc.peak_in_use,
            "pages_total": self.alloc.capacity - 1,
        }

    def report(self, wall: float) -> dict:
        # re-describe at report time: the decode step re-resolves its
        # route per trace (e.g. REPRO_PAGED_KERNEL flipped after
        # construction), and the report must state what actually ran
        self.plan = exec_plan.describe("paged_decode", self.pol,
                                       **self._plan_ctx)
        lat = np.array([r.t_finish - r.arrival for r in self.finished])
        ttft = np.array([r.t_first - r.arrival for r in self.finished])
        gen = sum(r.n_generated for r in self.finished)
        kv = self.kv_bytes_report()
        rep = {
            "n_requests": len(self.finished),
            "wall_s": wall,
            "steps": self.n_steps,
            "gen_tokens": gen,
            # 0.0 (not inf) on a zero-length wall: the report must stay
            # strict JSON (json.dumps(..., allow_nan=False) round-trips)
            "tokens_per_s": gen / wall if wall > 0 else 0.0,
            "p50_latency_s": float(np.percentile(lat, 50)) if len(lat) else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99)) if len(lat) else 0.0,
            "p50_ttft_s": float(np.percentile(ttft, 50)) if len(ttft) else 0.0,
            "decode_route": self.plan["route"],
            "decode_backend": self.plan["backend"],
            "decode_selection": self.plan["selection"],
            "decode_bytes_per_step_layer": self.plan["bytes_moved"],
            "temperature": self.sampler.temperature,
            **kv,
        }
        rep["tp"] = self.tp
        if self.ecfg.tp > 1:
            rep["tp_requested"] = self.ecfg.tp
            if self.tp_fallback:
                rep["tp_fallback_reason"] = self.tp_fallback
        if self.tp > 1:
            # wire + residency accounting from the *actual device
            # arrays*, not the bytes model: one decode step all-gathers
            # each layer's pool shards, so each device receives
            # (tp-1)/tp of the codes+scales pool per layer
            g = self.caches["groups"]["p0"]
            pool_layer = sum(int(g[k].nbytes)
                             for k in KV.QUANT_KEYS) // self._n_groups
            f32_layer = 2 * 4 * (self.ecfg.n_pages * self.ecfg.page_size
                                 * self.cfg.n_kv_heads * self.cfg.hd)
            frac = (self.tp - 1) / self.tp
            rep.update({
                "tp_wire_bytes_per_step_layer": int(frac * pool_layer),
                "tp_wire_reduction_vs_f32": f32_layer / pool_layer,
                "pool_bytes_per_device": kv["paged_bytes"] // self.tp,
            })
        if self.spec is not None:
            # re-describe like the decode plan above: the report states
            # which kernel drafted and which verified
            self.draft_plan = exec_plan.describe(
                "paged_decode", self.draft_pol, **self._plan_ctx)
            self.verify_plan = exec_plan.describe(
                "verify_attn", self.pol, sq=self.spec.k + 1,
                **self._plan_ctx)
            rep.update({
                "spec_draft_policy": self.spec.draft_policy,
                "spec_k": self.spec.k,
                "spec_rounds": self.spec_rounds,
                "acceptance_rate": (self.drafts_accepted / self.drafted
                                    if self.drafted else 0.0),
                # tokens one request advances per round it participates
                # in — the speculative speedup knob, in [1, k+1]
                "eff_tokens_per_round": (self.spec_emitted
                                         / self.spec_request_rounds
                                         if self.spec_request_rounds
                                         else 0.0),
                "draft_route": self.draft_plan["route"],
                "draft_backend": self.draft_plan["backend"],
                "verify_route": self.verify_plan["route"],
                "verify_backend": self.verify_plan["backend"],
            })
        if self.adaptive is not None:
            # per-rung breakdown; the global acceptance_rate stays the
            # drafted-token-weighted aggregate over rungs (== the old
            # scalar when the ladder has one rung)
            tw = sum(self.rung_wall)
            rungs = []
            for i, rg in enumerate(self.rungs):
                # re-describe per rung, like the decode plan above
                rg.plan = exec_plan.describe("paged_decode", rg.pol,
                                             **self._plan_ctx)
                rungs.append({
                    "policy": rg.name,
                    "k": rg.k,
                    "rounds": self.rung_rounds[i],
                    "drafted": self.rung_drafted[i],
                    "accepted": self.rung_accepted[i],
                    "acceptance_rate": (self.rung_accepted[i]
                                        / self.rung_drafted[i]
                                        if self.rung_drafted[i] else 0.0),
                    "emitted": self.rung_emitted[i],
                    "wall_share": (self.rung_wall[i] / tw
                                   if tw > 0 else 0.0),
                    "draft_route": rg.plan["route"],
                    "draft_backend": rg.plan["backend"],
                })
            rep.update({
                "adaptive_ladder": [rg.name for rg in self.rungs],
                "adaptive_switches": self.ctrl_switches,
                "adaptive_demotes": self.ctrl_demotes,
                "adaptive_promotes": self.ctrl_promotes,
                "adaptive_rungs": rungs,
                "spec_rounds": self.spec_rounds,
                "acceptance_rate": (self.drafts_accepted / self.drafted
                                    if self.drafted else 0.0),
                "eff_tokens_per_round": (self.spec_emitted
                                         / self.spec_request_rounds
                                         if self.spec_request_rounds
                                         else 0.0),
            })
        if self.prefix is not None:
            e, cfg, pol = self.ecfg, self.cfg, self.pol
            n_attn = self._n_groups + self._n_tail
            resident = KV.paged_kv_cache_nbytes(
                0, self.prefix.n_pages, e.page_size, cfg.n_kv_heads,
                cfg.hd, fmt=pol.fmt_kv, packed=pol.kv_packed)
            rep.update({
                "prefix_queries": self.prefix_queries,
                "prefix_hits": self.prefix_hits,
                "prefix_hit_rate": (self.prefix_hits / self.prefix_queries
                                    if self.prefix_queries else 0.0),
                "prefill_tokens_saved": self.prefill_tokens_saved,
                "prefix_cow_copies": self.cow_copies,
                "resident_prefix_pages": self.prefix.n_pages,
                # what keeping the cached prefixes warm actually costs at
                # format width (quantized pages make residency cheap)
                "resident_prefix_bytes": resident["paged"] * n_attn,
            })
        if self.cfg.is_moe:
            # re-describe like the decode plan: which grouped kernel the
            # expert contraction actually ran
            self.moe_plan = exec_plan.describe("grouped_matmul", self.pol,
                                               **self._moe_ctx)
            cfg = self.cfg
            n_mats = 3 if cfg.act == "silu" else 2
            n_w = (cfg.n_layers * n_mats * cfg.n_experts
                   * cfg.d_model * cfg.d_ff)
            w_bytes = operand_nbytes(n_w, self.pol.fmt_weights,
                                     packed=self.pol.packed)
            rep.update({
                "moe_experts": cfg.n_experts,
                "moe_top_k": cfg.top_k,
                "moe_grouped_route": self.moe_plan["route"],
                "moe_grouped_backend": self.moe_plan["backend"],
                "moe_grouped_selection": self.moe_plan["selection"],
                "moe_grouped_bytes_per_step_layer":
                    self.moe_plan["bytes_moved"],
                # expert weights through the grouped route's operand
                # interface, all layers x (gate/up/down) mats — vs the
                # f32 residency the seed's experts burned
                "expert_w_bytes": w_bytes,
                "expert_w_bytes_f32": 4 * n_w,
                "expert_w_reduction_vs_f32": 4 * n_w / w_bytes,
            })
        return rep


def format_report(rep: dict, policy: str) -> str:
    """The serve.py report lines: throughput/latency + honest cache bytes
    (counted from actual per-request lengths, not B x S_max) + page-
    allocator utilization."""
    mb = 1e6
    return (
        f"engine: {rep['n_requests']} reqs, {rep['gen_tokens']} tokens in "
        f"{rep['wall_s']:.2f}s ({rep['tokens_per_s']:.1f} tok/s, "
        f"{rep['steps']} steps, policy={policy})\n"
        f"latency: p50 {rep['p50_latency_s'] * 1e3:.0f} ms, "
        f"p99 {rep['p99_latency_s'] * 1e3:.0f} ms, "
        f"ttft p50 {rep['p50_ttft_s'] * 1e3:.0f} ms\n"
        f"kv-cache: peak live {rep['live_bytes'] / mb:.2f} MB "
        f"({rep['peak_live_tokens']} tokens) in "
        f"{rep['paged_bytes'] / mb:.2f} MB of pages vs static "
        f"{rep['static_bytes'] / mb:.2f} MB (B x S_max, same format) / "
        f"f32 {rep['static_f32_bytes'] / mb:.2f} MB; "
        f"page util peak {rep['page_util']:.0%} "
        f"({rep['pages_peak']}/{rep['pages_total']} pages)\n"
        f"plan: decode via {rep['decode_route']} "
        f"[{rep['decode_backend']}, {rep['decode_selection']}], "
        f"{rep['decode_bytes_per_step_layer'] / 1e3:.1f} KB KV moved "
        "per step/layer"
        + (f"\nspec: draft k={rep['spec_k']} under "
           f"{rep['spec_draft_policy']} via {rep['draft_route']} "
           f"[{rep['draft_backend']}], verify via {rep['verify_route']} "
           f"[{rep['verify_backend']}]; acceptance "
           f"{rep['acceptance_rate']:.0%}, "
           f"{rep['eff_tokens_per_round']:.2f} tokens/round over "
           f"{rep['spec_rounds']} rounds"
           if "spec_k" in rep else "")
        + ((f"\nadaptive: {len(rep['adaptive_rungs'])}-rung ladder, "
            f"{rep['adaptive_switches']} switches "
            f"({rep['adaptive_demotes']} demote, "
            f"{rep['adaptive_promotes']} promote); acceptance "
            f"{rep['acceptance_rate']:.0%}, "
            f"{rep['eff_tokens_per_round']:.2f} tokens/round over "
            f"{rep['spec_rounds']} rounds\n"
            + "\n".join(
                f"  rung {i}: {r['policy']} (k={r['k']}) acceptance "
                f"{r['acceptance_rate']:.0%}, {r['rounds']} rounds, "
                f"{r['drafted']} drafted, {r['emitted']} emitted, "
                f"{r['wall_share']:.0%} of spec wall via "
                f"{r['draft_route']} [{r['draft_backend']}]"
                for i, r in enumerate(rep["adaptive_rungs"])))
           if "adaptive_rungs" in rep else "")
        + (f"\nprefix: {rep['prefix_hits']}/{rep['prefix_queries']} hits "
           f"({rep['prefix_hit_rate']:.0%}), "
           f"{rep['prefill_tokens_saved']} prefill tokens saved, "
           f"{rep['prefix_cow_copies']} CoW copies; "
           f"{rep['resident_prefix_pages']} resident pages "
           f"({rep['resident_prefix_bytes'] / mb:.2f} MB at format width)"
           if "prefix_hit_rate" in rep else "")
        + (f"\ntp: {rep['tp']} devices on \"model\", pool "
           f"{rep['pool_bytes_per_device'] / mb:.2f} MB/device; wire "
           f"{rep['tp_wire_bytes_per_step_layer'] / 1e3:.1f} KB "
           f"codes+scales per step/layer "
           f"({rep['tp_wire_reduction_vs_f32']:.1f}x under an f32 wire)"
           if rep.get("tp", 1) > 1 else "")
        + (f"\ntp: requested {rep['tp_requested']}, serving replicated — "
           f"{rep['tp_fallback_reason']}"
           if "tp_fallback_reason" in rep else "")
        + (f"\nmoe: {rep['moe_experts']} experts top-{rep['moe_top_k']}, "
           f"grouped via {rep['moe_grouped_route']} "
           f"[{rep['moe_grouped_backend']}, "
           f"{rep['moe_grouped_selection']}]; expert weights "
           f"{rep['expert_w_bytes'] / mb:.2f} MB at format width vs f32 "
           f"{rep['expert_w_bytes_f32'] / mb:.2f} MB "
           f"({rep['expert_w_reduction_vs_f32']:.1f}x), "
           f"{rep['moe_grouped_bytes_per_step_layer'] / 1e3:.1f} KB "
           "expert operands per step/layer"
           if "moe_experts" in rep else ""))
