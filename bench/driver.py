"""Drive the serving engine with a traffic mix and stamp every token.

The engine is the program's ``ServingEngine`` on its default continuous
scheduler over paged KV, driven the way ``launch/serve.py`` drives it:
``submit()`` to enqueue and ``step()`` to run one scheduler iteration. The
benchmark stamps, on the host clock, when each request was due (open loop)
or sent (closed loop) and when each of its tokens came back from
``step()``; the engine reads its sampled tokens back every step, so a
stamp follows the device's step.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np


class Rec:
    """One request as the benchmark saw it."""
    __slots__ = ("uid", "client", "t0", "times", "done", "req", "meta",
                 "membership", "warm")

    def __init__(self, uid, client, t0, req, meta, warm=False):
        self.uid, self.client, self.t0 = uid, client, t0
        self.times, self.done, self.req, self.meta = [], None, req, meta
        self.membership, self.warm = None, warm


class Driver:
    def __init__(self, eng, traffic, *, annotate=False):
        self.eng, self.traffic = eng, traffic
        self.recs = {}
        self.annotate = annotate
        self.steps = 0
        self.late_s = []            # open loop: how late each send was
        self._pending = []          # open loop: (due, prompt, max_new, meta)
        self._steady_seen = set()
        self.step_log = None        # traced runs: per-step decode slots
        self.trace_t0 = None        # traced runs: when the trace started
        self.step_kind = {}         # step time -> which decode ran, prefill
        from repro.core import cache as chai_cache
        self._steady = chai_cache.PHASE_STEADY

    # -- host annotations (traced runs only) --------------------------------
    def _span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _step_span(self):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.StepTraceAnnotation("bench.step",
                                                step_num=self.steps)

    # -- requests -----------------------------------------------------------
    def submit(self, prompt, max_new, client, t0, meta, warm=False):
        with self._span("bench.submit"):
            req = self.eng.submit(prompt, max_new_tokens=max_new)
        self.recs[req.uid] = Rec(req.uid, client, t0, req, meta, warm)
        return req

    def _record(self, outs, kind):
        t = time.perf_counter()
        finished = []
        prefill = False
        for o in outs:
            rec = self.recs.get(o.uid)
            if rec is None:
                continue
            prefill |= not rec.times and bool(o.token_ids)
            rec.times.extend([t] * len(o.token_ids))
            if o.finished:
                rec.done = t
                finished.append(rec)
        # CHAI membership of each request, as chosen at its CLUSTER step
        # (a device slice, read back after the window). A request that
        # clustered and finished in this same step left its membership in
        # its slot's context, which only its slot's next CLUSTER rewrites.
        eng = self.eng
        if eng.chai_on and eng._dev_ctx is not None:
            for i, req in enumerate(eng._slot_req):
                if req is None or req.uid in self._steady_seen:
                    continue
                if eng._phases[i] == self._steady:
                    self._capture(req.uid, i)
            warm = eng.cfg.chai.warmup_tokens
            for rec in finished:
                if rec.uid not in self._steady_seen and \
                        len(rec.req.generated) > warm + 1:
                    self._capture(rec.uid, rec.req.slot)
        if self.step_log is not None:
            self._log_step(t, finished)
        self.step_kind[t] = kind + ("+prefill" if prefill else "")
        return t, finished

    def _decode_kind(self):
        """The decode program the engine's next step runs for the slots it
        holds now: all WARMUP, all STEADY, or a mix (``none`` if empty)."""
        from repro.core import cache as chai_cache
        ph = {int(self.eng._phases[i]) for i, r in
              enumerate(self.eng._slot_req) if r is not None}
        if not ph:
            return "none"
        if ph == {chai_cache.PHASE_STEADY}:
            return "steady"
        if ph == {chai_cache.PHASE_WARMUP}:
            return "warmup"
        return "mixed"

    def _capture(self, uid, slot):
        self._steady_seen.add(uid)
        rec = self.recs.get(uid)
        if rec is not None:
            rec.membership = {k: v[:, slot] for k, v in
                              self.eng._dev_ctx.items()}

    def _log_step(self, t, finished):
        """(time, [(phase, kv_len, cached_tokens, prefix id)]) of every slot
        that decoded this step; a slot retired this step decoded in STEADY
        or WARMUP as its last token shows."""
        from repro.core import cache as chai_cache
        eng = self.eng
        rows = []
        live = {chai_cache.PHASE_WARMUP, chai_cache.PHASE_STEADY}
        for i, req in enumerate(eng._slot_req):
            if req is not None and int(eng._phases[i]) in live:
                rows.append(self._row(req, int(eng._phases[i])))
        for rec in finished:
            req = rec.req
            if len(req.generated) > 1:
                steady = (eng.chai_on and len(req.generated)
                          > eng.cfg.chai.warmup_tokens + 1)
                rows.append(self._row(req, chai_cache.PHASE_STEADY if steady
                                      else chai_cache.PHASE_WARMUP))
        self.step_log.append((t, rows))

    def _row(self, req, phase):
        rec = self.recs.get(req.uid)
        pre = rec.meta.get("prefix", -1) if rec is not None else -1
        return (phase, len(req.prompt) + len(req.generated) - 1,
                int(req.cached_tokens), pre)

    def step(self):
        kind = self._decode_kind()
        with self._step_span():
            outs = self.eng.step()
        self.steps += 1
        with self._span("bench.record"):
            return self._record(outs, kind)

    # -- closed loop --------------------------------------------------------
    def closed_fill(self):
        """One request per client; client c's first answer is cut to
        (c + 1) / clients of its drawn length, so that first requests end
        staggered as in a running deployment."""
        n = self.traffic.clients
        for c in range(n):
            prompt, max_new, meta = self.traffic.next_request(c)
            max_new = max(1, (max_new * (c + 1)) // n)
            self.submit(prompt, max_new, c, time.perf_counter(), meta)

    def closed_refill(self, finished):
        for rec in finished:
            if rec.client < 0:
                continue
            prompt, max_new, meta = self.traffic.next_request(rec.client)
            self.submit(prompt, max_new, rec.client, time.perf_counter(),
                        meta)

    def run_steps(self, n):
        """Closed loop for exactly ``n`` steps (CPU rehearsal)."""
        for _ in range(n):
            _, finished = self.step()
            self.closed_refill(finished)

    def run_closed(self, t_end):
        while time.perf_counter() < t_end:
            _, finished = self.step()
            self.closed_refill(finished)

    # -- open loop ----------------------------------------------------------
    def open_schedule(self, t_start, seconds):
        self._pending = [(t_start + off, p, m, meta) for off, p, m, meta in
                         self.traffic.schedule(seconds)]
        self._pending.reverse()

    def _send_due(self):
        now = time.perf_counter()
        while self._pending and self._pending[-1][0] <= now:
            due, prompt, max_new, meta = self._pending.pop()
            self.late_s.append(now - due)
            self.submit(prompt, max_new, -1, due, meta)

    def run_open(self, t_end):
        eng = self.eng
        while time.perf_counter() < t_end:
            self._send_due()
            if not eng.has_work():
                nxt = self._pending[-1][0] if self._pending else t_end
                with self._span("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_end)
                                   - time.perf_counter()))
                continue
            self.step()

    def run(self, t_end):
        if self.traffic.loop == "closed":
            self.run_closed(t_end)
        else:
            self.run_open(t_end)

    # -- warm-up ------------------------------------------------------------
    def warm_shapes(self, lengths):
        """Compile the prefill programs the traffic will use: one request of
        one answer token per prompt-length bucket, retired at admission.
        With shared prefixes, each prefix is first prefilled whole and the
        buckets are those of the suffixes (suffix prefill)."""
        eng = self.eng
        tr = self.traffic
        for p in tr.prefixes:
            self._warm_one(np.concatenate([p, tr._tokens(1)]))
        pre = tr.prefixes[0] if tr.prefixes else np.zeros((0,), np.int32)
        seen = set()
        for n in lengths:
            n_new = n - len(pre)
            key = eng._prompt_bucket(n_new, eng.ecfg.max_seq)
            if key not in seen:
                seen.add(key)
                self._warm_one(np.concatenate([pre, tr._tokens(n_new)]))

    def warm_decode(self):
        """Compile the batched decode programs for the live state (all-
        WARMUP, all-STEADY and mixed), so a phase mix first met in the
        window finds its program compiled. Lowering reads the arrays'
        shapes and placements only; nothing runs."""
        import jax
        import jax.numpy as jnp
        eng = self.eng
        eng._ensure_dev_state()
        args = (eng.params, {"tokens": eng._next_tok_dev}, eng._dev_state)
        eng._mha_step.lower(*args).compile()
        if eng.chai_on:
            ctx = eng._dev_ctx
            for step in (eng._chai_step, eng._mixed_step):
                step.lower(*args, ctx).compile()
            pages = eng._page_vec([])
            eng._cluster_fn().lower(eng._dev_state, ctx, jnp.int32(0), pages,
                                    pages).compile()
            # The membership capture's slice of one slot.
            jax.block_until_ready({k: v[:, 0] for k, v in ctx.items()})

    def warm_relay(self):
        """With shared prefixes and relay decode on: compile the relay step
        for every group signature the traffic can form (1 to as many
        groups as prefixes, 2 to all slots in the largest group), with
        inputs built as the engine builds them (packed prefix pages stacked
        per group, host row maps)."""
        import jax.numpy as jnp
        eng, tr = self.eng, self.traffic
        if not (eng.relay_decode and tr.prefixes):
            return
        b, ps = eng.ecfg.batch_slots, eng.ecfg.page_size
        n_pages = min(len(p) for p in tr.prefixes) // ps
        # The engine uploads a chain's page ids as a tuple of ints.
        null = jnp.asarray((0,) * n_pages, jnp.int32)
        pack = eng._pack_prefix_fn(n_pages)(eng._dev_state, null, null)
        args = (eng.params, {"tokens": eng._next_tok_dev}, eng._dev_state,
                eng._dev_ctx)
        for g in range(1, len(tr.prefixes) + 1):
            kv = {k: jnp.stack([pack[k]] * g, axis=1) for k in pack}
            for nmax in range(max(1, eng.ecfg.relay_min_group), b + 1):
                shapes = relay_structs(eng.cfg, b, g, nmax, n_pages * ps)
                relay = {k: kv[k] if k in kv else
                         jnp.asarray(np.zeros(v.shape, v.dtype))
                         for k, v in shapes.items()}
                eng._relay_step.lower(*args, relay).compile()

    def _warm_one(self, prompt):
        self.submit(prompt, 1, -1, time.perf_counter(), {}, warm=True)
        while self.eng.has_work():
            self.step()

    def until_steady(self, max_steps=100000):
        """Step (refilling closed-loop clients) until every slot has held a
        STEADY request at least once."""
        eng = self.eng
        b = eng.ecfg.batch_slots
        reached = set()
        for _ in range(max_steps):
            _, finished = self.step()
            if self.traffic.loop == "closed":
                self.closed_refill(finished)
            for i in range(b):
                if eng._slot_req[i] is not None and \
                        eng._phases[i] == self._steady:
                    reached.add(i)
            if len(reached) == b or not eng.has_work():
                return

    # -- readings -----------------------------------------------------------
    def window_stats(self, t0, t1):
        """TTFT of every request whose first token fell in [t0, t1), every
        gap between consecutive tokens of a request that ended in it, and
        the tokens emitted in it."""
        ttft, itl, kinds, n_tok, first = [], [], [], 0, 0
        for rec in self.recs.values():
            if rec.warm or not rec.times:
                continue
            ts = rec.times
            if t0 <= ts[0] < t1:
                ttft.append(ts[0] - rec.t0)
                first += 1
            for a, b in zip(ts, ts[1:]):
                if t0 <= b < t1 and b > a:
                    itl.append(b - a)
                    kinds.append(self.step_kind.get(b, "?"))
            n_tok += sum(1 for t in ts if t0 <= t < t1)
        return {"ttft": ttft, "itl": itl, "itl_kind": kinds,
                "tokens": n_tok, "first_tokens": first}

    def finished_in(self, t0, t1):
        out = []
        for rec in self.recs.values():
            if rec.warm or rec.done is None or not (t0 <= rec.done < t1):
                continue
            out.append(rec)
        return out


def relay_structs(cfg, slots, groups, nmax, prefix_len):
    """Shapes of the relay dict the engine builds for ``groups`` groups of
    at most ``nmax`` members over a ``prefix_len``-token shared prefix
    (bf16 MHA layout; see ``EngineCore._build_relay``)."""
    import jax
    import jax.numpy as jnp
    ng, na = cfg.n_global_layers, cfg.n_attn_layers
    h, r, hd = cfg.n_heads, cfg.k_max, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    kv = jax.ShapeDtypeStruct((ng, groups, cfg.n_kv_heads, prefix_len, hd),
                              dt)
    return {"k": kv, "v": kv, "k_row": i32(na, groups, nmax * r),
            "a_row": i32(na, groups, nmax * h),
            "v_row": i32(na, groups, nmax * h), "plen": i32(groups),
            "members": i32(groups, nmax), "gid": i32(slots),
            "midx": i32(slots), "len": i32(slots),
            "in_group": jax.ShapeDtypeStruct((slots,), jnp.bool_)}

