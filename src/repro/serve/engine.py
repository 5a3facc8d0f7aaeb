"""Continuous-batching serving engine (slot scheduler + paged decode).

The MaxText MLPerf-offline serving shape, grown onto this repo's stack:

  * fixed-capacity SLOTS hold in-flight requests; device state
    (page pool, per-slot next-token, output buffer) is shape-static;
  * a prefill -> insert -> generate loop: finished slots are evicted and
    refilled MID-FLIGHT from the waiting queue without recompiling —
    exactly TWO AOT executables (admit, decode) serve the entire trace,
    and ``n_compiles`` is exported so tests/CI can assert the
    one-executable contract as slots churn;
  * request arrivals come from the shared ``sim.events`` queue
    (``KIND_ARRIVE``; Poisson/diurnal — see ``serve.arrivals``), popped
    against the engine's virtual clock like the async FL engine pops
    completions;
  * the virtual clock + §IV.F accounting (Eq. 4 cold/warm container
    delay on each admission, energy-per-token, cold-start energy) ride
    ``serve.costs.ServeCostModel`` on the same ``FaasSimConfig`` as the
    FL round engines;
  * generated tokens land in a device-resident ``(max_requests+1,
    max_gen)`` buffer via per-slot routing vectors — the host never syncs
    tokens during the loop; ONE terminal device->host transfer yields
    every request's output (`ServeReport.tokens`).

Correctness contract (tests/test_serving.py): with ``attn="dense"`` the
engine reproduces the sequential per-request oracle token-for-token on
non-MoE families; ``attn="paged"`` swaps in the Pallas paged
flash-decode kernel (fp32-tolerance logits, same greedy tokens).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.models.config import Family
from repro.models.transformer import Runtime
from repro.serve.arrivals import RequestTrace
from repro.serve.costs import ServeCostModel
from repro.serve.paged import PagePlan, init_pool, make_admit_fn, make_decode_fn
from repro.serve.scheduler import PageAllocator, SlotScheduler
from repro.sim.events.queue import peek_time, pop_event

# The queue ops run between compiled steps; jitted once (per queue
# capacity) they cost one dispatch instead of ~10 eager primitive binds —
# the arrival process must not tax the decode loop it drives. No donation:
# the first pop's operand is the trace's own queue, which must survive so
# one trace can be served repeatedly (oracle vs engine, timing reps).
_peek = jax.jit(peek_time)
_pop = jax.jit(pop_event)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    slots: int = 8
    page_size: int = 16
    prompt_len: int = 16
    max_gen: int = 16  # per-request generation cap (sizes slot span)
    max_requests: int = 256  # output-buffer rows; traces must fit
    num_pages: int = 0  # physical pool size; 0 = slots * pages_per_slot
    attn: str = "dense"  # "dense" (oracle-exact) | "paged" (Pallas kernel)
    policy: str = "fifo"  # waiting-queue order: "fifo" | "edf"
    max_queue: int = 0  # admission cap (0 = unbounded); over -> rejected
    n_patches: int = 8  # VLM frontend tokens per request


@dataclasses.dataclass
class ServeReport:
    """Everything one trace produced (host-side; device synced once)."""

    n_requests: int
    completed: int
    rejected: int
    slo_violations: int
    tokens_generated: int
    decode_steps: int
    prefills: int
    cold_starts: int
    virtual_ms: float
    wall_s: float
    latency_ms: np.ndarray  # (R,) NaN for rejected
    percentiles: dict[str, float]  # p50/p95/p99 over completed requests
    goodput_rps: float  # SLO-met completions per virtual second
    tokens_per_s: float  # virtual-time throughput
    tokens_per_wall_s: float  # wall-clock throughput (the benchmark axis)
    energy_j: float
    energy_per_token_j: float
    n_compiles: dict[str, int]
    counters: dict[str, int]
    tokens: np.ndarray  # (R, max_gen) int32; row r valid to gen_len[r]
    gen_len: np.ndarray  # (R,)

    def tokens_for(self, req: int) -> list[int]:
        return self.tokens[req, : int(self.gen_len[req])].tolist()


def _aval(x):
    return jax.ShapeDtypeStruct(x.shape, jnp.asarray(x).dtype)


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a fixed page pool."""

    def __init__(
        self,
        model: Model,
        params,
        cfg: EngineConfig = EngineConfig(),
        cost: ServeCostModel = ServeCostModel(),
        runtime: Runtime = Runtime(),
        tap=None,
        interpret: bool | None = None,
    ):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.cost = cost
        self.tap = tap
        self.plan = PagePlan.build(
            model.cfg, cfg.prompt_len, cfg.max_gen,
            page_size=cfg.page_size, n_patches=cfg.n_patches,
        )
        self.num_pages = cfg.num_pages or cfg.slots * self.plan.pages_per_slot
        if self.plan.pages_per_slot > self.num_pages:
            raise ValueError(
                f"pool of {self.num_pages} pages cannot hold one request "
                f"({self.plan.pages_per_slot} pages)"
            )
        self.is_vlm = model.cfg.family is Family.VLM
        self.is_ssm = model.cfg.family is Family.SSM

        s, plan = cfg.slots, self.plan
        pool_avals = jax.eval_shape(
            lambda: init_pool(model.cfg, plan, s, self.num_pages)
        )
        tok_aval = jax.ShapeDtypeStruct((s, 1), jnp.int32)
        buf_aval = jax.ShapeDtypeStruct(
            (cfg.max_requests + 1, cfg.max_gen), jnp.int32
        )
        i32 = jnp.int32

        admit = make_admit_fn(model, plan, runtime)
        admit_avals = [_aval(np.zeros((1, plan.prompt_len), np.int32))]
        if self.is_vlm:
            admit_avals.append(
                jax.ShapeDtypeStruct(
                    (1, plan.n_patches, model.cfg.d_model),
                    jnp.dtype(model.cfg.compute_dtype),
                )
            )
        admit_avals += [
            jax.ShapeDtypeStruct((plan.prompt_pages,), i32),
            jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((), i32),
        ]
        self._admit = (
            jax.jit(admit, donate_argnums=(1, 2, 3))
            .lower(params, pool_avals, tok_aval, buf_aval, *admit_avals)
            .compile()
        )
        step = make_decode_fn(model, plan, runtime, cfg.attn, interpret)
        step_avals = [
            jax.ShapeDtypeStruct((s, plan.pages_per_slot), i32),  # page_table
            jax.ShapeDtypeStruct((s,), i32),  # positions
            jax.ShapeDtypeStruct((s,), jnp.bool_),  # active
            jax.ShapeDtypeStruct((s,), i32),  # out_req
            jax.ShapeDtypeStruct((s,), i32),  # out_idx
        ]
        self._decode = (
            jax.jit(step, donate_argnums=(1, 2, 3))
            .lower(params, pool_avals, tok_aval, buf_aval, *step_avals)
            .compile()
        )
        # The one-executable contract: these never change after __init__.
        self.n_compiles = {"admit": 1, "decode": 1}

    # ------------------------------------------------------------------ #
    def decode_hlo_text(self) -> str:
        """Compiled decode HLO — launch/serve.py runs its collective
        census over this, same as the static path."""
        return self._decode.as_text()

    # ------------------------------------------------------------------ #
    def serve(self, trace: RequestTrace, max_steps: int = 0) -> ServeReport:
        cfg, plan, cost = self.cfg, self.plan, self.cost
        r = trace.n_requests
        if r > cfg.max_requests:
            raise ValueError(f"trace of {r} > max_requests={cfg.max_requests}")
        if trace.prompts.shape[1] != plan.prompt_len:
            raise ValueError("trace prompt_len != engine prompt_len")
        if int(trace.gen_len.max()) > plan.max_gen or int(trace.gen_len.min()) < 1:
            raise ValueError("trace gen_len outside [1, max_gen]")
        if plan.pages_for_gen(int(trace.gen_len.max())) > self.num_pages:
            raise ValueError("a request needs more pages than the pool holds")

        sched = SlotScheduler(cfg.slots, cfg.max_queue, cfg.policy)
        alloc = PageAllocator(self.num_pages)
        pool = init_pool(self.model.cfg, plan, cfg.slots, self.num_pages)
        tokens = jnp.zeros((cfg.slots, 1), jnp.int32)
        out_buf = jnp.zeros((cfg.max_requests + 1, cfg.max_gen), jnp.int32)

        n_tab = plan.pages_per_slot
        page_table = np.zeros((cfg.slots, n_tab), np.int32)
        positions = np.zeros((cfg.slots,), np.int32)
        active = np.zeros((cfg.slots,), bool)
        out_req = np.full((cfg.slots,), cfg.max_requests, np.int32)  # trash row
        out_idx = np.zeros((cfg.slots,), np.int32)

        queue = trace.queue
        vclock = 0.0
        last_busy = -math.inf  # first admission is always a cold start
        latency = np.full((r,), np.nan)
        fpt = self.model.flops_per_token(train=False)
        prompt_flops = fpt * plan.prompt_eff
        energy = 0.0
        cold_starts = prefills = decode_steps = tokens_generated = 0
        slo_violations = 0

        def finish(slot: int) -> None:
            nonlocal slo_violations
            st = sched.on_complete(slot)
            alloc.free(st.pages)
            latency[st.req] = vclock - float(trace.arrival_ms[st.req])
            slo_violations += vclock > st.deadline_ms
            page_table[slot] = 0
            positions[slot] = 0
            active[slot] = False
            out_req[slot] = cfg.max_requests
            out_idx[slot] = 0

        wall0 = time.perf_counter()
        while sched.completed + sched.rejected < r:
            # 1. Drain arrivals that are due at the current virtual time.
            while True:
                t = float(_peek(queue))
                if not t <= vclock:
                    break
                ev, queue = _pop(queue)
                req = int(ev.payload)
                sched.on_arrival(req, t + trace.slo_ms)
            # 2. Refill free slots from the waiting queue (policy order).
            while True:
                nxt = sched.next_fill()
                if nxt is None:
                    break
                req, deadline = nxt
                gen = int(trace.gen_len[req])
                pages = alloc.alloc(plan.pages_for_gen(gen))
                if pages is None:
                    break  # pool exhausted; retry after evictions
                warm = (vclock - last_busy) <= cost.keep_alive_ms
                slot = sched.on_insert(req, pages, gen - 1, deadline)
                row = np.zeros((n_tab,), np.int32)
                row[: len(pages)] = pages
                admit_args = [trace.prompts[req][None]]
                if self.is_vlm:
                    admit_args.append(trace.patch_embeds[req][None])
                pool, tokens, out_buf = self._admit(
                    self.params, pool, tokens, out_buf, *admit_args,
                    row[: plan.prompt_pages], np.int32(slot), np.int32(req),
                )
                vclock += cost.prefill_ms(prompt_flops, warm)
                energy += cost.prefill_energy_j(prompt_flops, warm)
                cold_starts += not warm
                prefills += 1
                tokens_generated += 1  # prefill emits the first token
                last_busy = vclock
                if sched.slots[slot].remaining == 0:
                    finish(slot)  # gen_len == 1: done at prefill
                    continue
                page_table[slot] = row
                positions[slot] = plan.prompt_eff
                active[slot] = True
                out_req[slot] = req
                out_idx[slot] = 1
            # 3. Idle: jump the clock to the next arrival.
            if not active.any():
                t = float(_peek(queue))
                if math.isinf(t):
                    assert not sched.waiting, "stuck with waiting requests"
                    continue  # loop condition decides termination
                vclock = max(vclock, t)
                continue
            # 4. One batched decode step — THE compiled executable. The
            # slot arrays are mutated in place right after this async
            # dispatch, and the CPU client may alias numpy buffers
            # instead of copying them, so the step gets snapshots.
            pool, tokens, out_buf = self._decode(
                self.params, pool, tokens, out_buf,
                *(a.copy() for a in (page_table, positions, active,
                                     out_req, out_idx)),
            )
            n_active = int(active.sum())
            decode_steps += 1
            tokens_generated += n_active
            vclock += cost.decode_step_ms(fpt * n_active)
            energy += cost.step_energy_j(fpt * n_active, n_active)
            last_busy = vclock
            if self.tap is not None:
                self.tap.host_log(
                    {
                        "virtual_ms": vclock,
                        "active_slots": n_active,
                        "waiting": len(sched.waiting),
                        "completed": sched.completed,
                        "tokens_generated": tokens_generated,
                        "energy_j": energy,
                    },
                    step=decode_steps,
                )
            # 5. Advance live slots; evict the finished ones.
            for slot in np.nonzero(active)[0]:
                positions[slot] += 1
                out_idx[slot] += 1
                st = sched.slots[slot]
                st.remaining -= 1
                if st.remaining == 0:
                    finish(int(slot))
            if max_steps and decode_steps >= max_steps:
                break

        # ONE terminal device->host sync for every request's tokens.
        tokens_np = np.asarray(jax.block_until_ready(out_buf))[: r]
        wall = time.perf_counter() - wall0

        counters = sched.conservation()
        done = ~np.isnan(latency)
        lat_done = latency[done]
        pct = {
            f"p{p}": float(np.percentile(lat_done, p)) if lat_done.size else float("nan")
            for p in (50, 95, 99)
        }
        in_slo = int(np.sum(lat_done <= trace.slo_ms)) if lat_done.size else 0
        vsec = max(vclock / 1e3, 1e-9)
        return ServeReport(
            n_requests=r,
            completed=sched.completed,
            rejected=sched.rejected,
            slo_violations=slo_violations,
            tokens_generated=tokens_generated,
            decode_steps=decode_steps,
            prefills=prefills,
            cold_starts=cold_starts,
            virtual_ms=vclock,
            wall_s=wall,
            latency_ms=latency,
            percentiles=pct,
            goodput_rps=in_slo / vsec,
            tokens_per_s=tokens_generated / vsec,
            tokens_per_wall_s=tokens_generated / max(wall, 1e-9),
            energy_j=energy,
            energy_per_token_j=energy / max(tokens_generated, 1),
            n_compiles=dict(self.n_compiles),
            counters=counters,
            tokens=tokens_np,
            gen_len=trace.gen_len.copy(),
        )
