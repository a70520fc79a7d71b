"""Drafting on the device: self-speculative decoding whose drafter is a MODULE
OF THE MODEL (a multi-token-prediction module, ``models/mtp.py``,
``spec_draft: "module"``), the part of :class:`~deepspeed_tpu.inference.
scheduler.DecodeScheduler` that only such a scheduler runs.

A host-side drafter (``speculative.PromptLookupDrafter``) reads the accepted
tokens, so its pump is serial. This drafter needs the stack's last hidden
state, which never leaves the device, so the whole of a step stays there:

- a decode step feeds each live row TWO columns ``[t, d]`` at its write head
  ``p``: its last token and the module's draft of the next. The stack samples
  ``s0`` (the token after ``t``) and ``s1`` (the token after ``d``), each with
  the request's own keys at its absolute step index; ``d == s0`` commits both
  (the row advances 2), else ``s0`` alone (advances 1). The emitted stream is
  the stream without the module, greedy and sampled;
- the rejected column's rows are void. A row cache holds them past the write
  head, where the position's true row overwrites them before any query reads
  so far; a windowed layer's ring rolls back by position too
  (``models/transformer.py: _ring_attention``: the void row is taken for the
  position a ring's length before it, which no later window reaches); the
  module's own rows are written for committed pairs only and have no holes;
- the module then runs over the committed pairs ``(g_p, s0)`` and, if
  accepted, ``(g_(p+1), s1)`` (``g``: the stack's normed output), writes its
  K/V rows and drafts the token after the last committed one. Through a
  prompt it consumes every ``(g_i, t_(i+1))`` chunk by chunk, the next
  chunk's first token (or, behind the last chunk, the sampled token 0) closing
  each chunk;
- each of a sync's ``steps_per_sync`` steps does all of that; the next step's
  ``[t, d]``, write head and sampling step are carried ON THE DEVICE, within
  a sync and from a sync to the next one launched ahead of its landing
  (:func:`_merge_carried_draft`, as ``scheduler._merge_carried`` carries the
  last token). The host learns the advances when the sync lands.

What the host knows at a launch is therefore an upper bound: a row in flight
is booked at 2 tokens a step (``_Request.inflight``, ``cache.lengths``) and
the landing books back what was not committed. A row whose budget COULD end
inside the sync in flight sits the next launch out and, if it did not end,
rejoins from the host's view, the draft included (``_Request.draft``). A
request reserves ``max_new_tokens + 2 * steps_per_sync`` rows.

One program (:meth:`DeviceDraft._draft_fn`) in the variants of
``_fused_fn``: width 2 (pure decode) or ``prefill_chunk`` (a chunk sync: the
decode rows' two columns, then the chunk as a ``(1, prefill_chunk)`` forward
over its own slot, then the remaining steps), two step counts, greedy /
sampling, logits collection. Counters: ``serving/spec_steps``,
``spec_draft_tokens`` (drafts verified: one a live row a step),
``spec_accepted_tokens``, ``spec_verify_columns`` (columns the verify
computed), ``spec_rows_void`` (those of them not committed),
``ring_rows_rewritten`` (ring rows written for a void column, one a windowed
layer), histogram ``serving/spec_tokens_per_step`` (a sync's mean).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .kv_cache import slot_slice, slot_update
from .sync import _CARRIED, _Flight, _replicate_logits, _sampler


def _merge_carried_draft(ids, lens, steps, block):
    """The inputs of a sync launched ahead: a row flagged ``_CARRIED`` in
    column 0 of ``ids`` takes its token, draft, write head and sampling step
    from the last four rows of ``block``, the result block of the sync in
    flight (:meth:`DeviceDraft._draft_fn`); the others keep what the host
    gave them."""
    tok, draft, head, step = block[-4:]
    carried = ids[:, 0] == _CARRIED
    ids = ids.at[:, 0].set(jnp.where(carried, tok, ids[:, 0]))
    ids = ids.at[:, 1].set(jnp.where(carried, draft, ids[:, 1]))
    return ids, jnp.where(carried, head, lens), jnp.where(carried, step, steps)


class DeviceDraft:
    """The device drafter's launches, landing and step program; mixed into
    :class:`DecodeScheduler`, whose constructor binds them in place of the
    plain ones where ``spec_draft == "module"`` (nothing here runs, and no
    sync pays for it, otherwise)."""

    def _init_device_draft(self):
        cfg = self.engine.module.cfg
        # void columns stay committed (every draft counts as accepted): the
        # control of the serving benchmark's comparison, never a way to serve
        self._draft_keeps_void = False
        self._ring_layers = sum(bool(cfg.layer_window(i)) for i in range(cfg.num_layers))
        self._draft_merge = jax.jit(_merge_carried_draft,
                                    out_shardings=(self._ids_sharding, ) * 3)
        self.spec_rows_void = 0

    # ------------------------------------------------------------------ launches
    def _draft_inputs(self, ids, lens, steps):
        """The host's ids, write heads and sampling steps on the device, the
        carried rows filled in from the sync in flight. All committed and
        replicated, merged or not, so that a step program is built once."""
        put = lambda x: jax.device_put(x, self._ids_sharding)
        dev = put(ids), put(lens), put(steps)
        if self._flight is not None and (ids[:, 0] == _CARRIED).any():
            dev = self._draft_merge(*dev, self._flight.out[0])
        return dev

    def _draft_rows(self):
        """The decode rows of the next launch and each one's second column:
        its draft, as the last landing left it, or nothing where the row is
        carried (the merge fills it in beside the token)."""
        live = self._live_rows()
        return live, [(0 if r.inflight else r.draft, ) for _, r in live]

    def _draft_decode_step(self):
        """Launch a pure decode sync of ``steps_per_sync`` verify-and-draft
        steps. Returns the flight, or None when every active row may end
        inside the sync in flight."""
        with self._span("sched/assemble"):
            live, drafts = self._draft_rows()
            if not live:
                return None
            ops = self._assemble(live, 2, more=drafts)
            K = self.steps_per_sync
            fn = self._draft_fn(ops.sampling, ops.collect, K, 2)
            args = self._step_args(ops, drafts=True)
        out = self._dispatch(fn, args, ops.spans, ops.lens)
        self.cache.pool = out[0]
        self._advance(live, 2 * K)
        return _Flight(out[1:], K, ops.collect, live)

    def _draft_chunk_step(self):
        """Launch a chunk sync: the decode rows' verify-and-draft step beside
        up to ``prefill_chunk`` prompt tokens of the row in the prefill lane,
        through the stack and the module, then the sync's remaining steps
        (which a final chunk's row joins with its sampled token 0 and the
        module's draft behind it). The lane advances here, at the launch, as
        in :meth:`DecodeScheduler._fused_chunk_step`."""
        pf = self._prefill
        preq, ps = pf.req, pf.req.slot
        C, L = self.prefill_chunk, pf.req.prompt.size
        take = min(C, L - pf.pos)
        final = pf.pos + take >= L
        with self._span("sched/assemble"):
            live, drafts = self._draft_rows()
            ops = self._assemble(live, C, more=drafts, prefill=(preq, pf.pos, take))
            # the counters see the chunk's take; the program finds the chunk
            # by ``chunk_ops``, and its row's span is 0
            counted = ops.spans.copy()
            ops.spans[ps] = 0
            K = self.steps_per_sync if (live or final or any(
                r.inflight for r in self.active.values())) else 1
            # the token behind the chunk, which closes its last pair: the next
            # chunk's first (a final chunk's is sampled in the program)
            chunk_ops = np.asarray([ps, take, int(final),
                                    0 if final else preq.prompt[pf.pos + take]], np.int32)
            fn = self._draft_fn(ops.sampling, ops.collect, K, C)
            args = self._step_args(ops, extra=(jnp.asarray(chunk_ops), ), drafts=True)
        out = self._dispatch(fn, args, counted, ops.lens, chunk=(ps, final))
        self.cache.pool = out[0]
        self._advance(live, 2 * K)
        fl = _Flight(out[1:], K, ops.collect, live, (preq, pf.pos, take, final))
        pf.pos += take
        if final:
            # booked at the most the row can do: token 0, then 2 a substep
            self.cache.lengths[ps] = L + 2 * (K - 1)
            preq.inflight += 1 + 2 * (K - 1)
            self._book_decode_row(preq)
        else:
            self.cache.lengths[ps] = pf.pos
        return fl

    # ------------------------------------------------------------------ landing
    def _land_draft(self, fl):
        """Land a verify-and-draft sync: fetch its result block (tokens,
        advances, the state behind it), book back what the launch booked and
        the device did not commit, deliver every row's committed tokens and
        keep its draft for a launch that finds it off the device."""
        K, N = fl.K, self.cache.num_slots
        with self._span("sched/fetch"):
            block, *rest = fl.out
            self._pop_expert_stats(rest)
            logits = dlogits = first_choice = choice = None
            if fl.collect:
                logits, dlogits = (np.asarray(x, np.float32)
                                   for x in jax.device_get(rest[:2]))  # (K, 2, N, V)
                if len(rest) == 4:
                    first_choice, choice = (np.asarray(x) for x in jax.device_get(rest[2:]))
            block = np.asarray(jax.device_get(block))
        self._landed(fl.program)
        toks = block[:2 * K].reshape(K, 2, N)
        adv = block[2 * K:3 * K]
        draft = block[3 * K + 1]
        self._steps += K
        # (slot, request, tokens the launch booked, whether it is a final
        # chunk's row: token 0 in step 0, then a decode row like the others)
        rows = [(s, r, 2 * K, False) for s, r in fl.rows]
        if fl.chunk is not None:
            preq, pos, take, final = fl.chunk
            tr = preq.trace
            if tr is not None and tr.enabled:
                fid = self._trace_link(tr)
                tr.phase("prefill_chunk", start=fl.t0, flow_in=[fid] if fid else None,
                         pos=int(pos), take=int(take), final=bool(final))
            if final:
                rows.append((preq.slot, preq, 1 + 2 * (K - 1), True))
            elif preq.done:
                self._count_discarded(1)
            elif first_choice is not None and preq.collect_logits:
                preq.choice.append(first_choice[:, :take])
        delivered = discarded = row_steps = accepted = 0
        with self._span("sched/deliver"):
            for slot, req, booked, joined in rows:
                req.inflight -= booked
                if req.done:
                    discarded += 1
                    continue
                a = adv[:, slot]
                self.cache.lengths[slot] -= booked - int(a.sum())
                req.draft = int(draft[slot])
                row_steps += int(np.count_nonzero(a[1:] if joined else a))
                accepted += int(np.count_nonzero(a == 2))
                keeps = req.collect_logits and logits is not None
                if keeps and joined and first_choice is not None:
                    req.choice.append(first_choice[:, :fl.chunk[2]])
                for k in range(K):
                    first = joined and k == 0
                    for j in range(int(a[k])):
                        if req.done:
                            break
                        if keeps:
                            req.logits.append(logits[k, j, slot])
                            req.draft_logits.append(dlogits[k, j, slot])
                            if choice is not None and not first:
                                req.choice.append(choice[k][:, slot, j:j + 1])
                        tok = int(toks[k, j, slot])
                        if first:
                            self._first_token(req, tok, None)
                        else:
                            self._deliver(req, tok)
                        delivered += 1
            self._landing_delivered()
        self._count_discarded(discarded)
        void = row_steps - accepted
        self.spec_steps += K
        self.spec_row_steps += row_steps
        self.spec_drafted += row_steps
        self.spec_accepted += accepted
        self.spec_delivered += delivered
        self.spec_rows_void += void
        tel = self.telemetry
        if tel.enabled and row_steps:
            tel.counter("serving/spec_steps", K)
            tel.counter("serving/spec_draft_tokens", row_steps)
            tel.counter("serving/spec_accepted_tokens", accepted)
            tel.counter("serving/spec_verify_columns", 2 * row_steps)
            tel.counter("serving/spec_rows_void", void)
            tel.counter("serving/ring_rows_rewritten", void * self._ring_layers)
            tel.histogram("serving/spec_tokens_per_step", (row_steps + accepted) / row_steps)
            tel.gauge("serving/spec_acceptance_rate",
                      self.spec_accepted / max(1, self.spec_drafted))
        self._observe(delivered, K, fl.t0)
        return delivered

    # ------------------------------------------------------------------ the program
    def _draft_fn(self, sampling, collect, ksteps, width):
        """THE verify-and-draft program (module docstring): ``ksteps`` steps
        over a ``(num_slots, width)`` ids block, ``width`` 2 for a pure decode
        sync or ``prefill_chunk`` for a chunk sync, which takes one more
        operand, ``(prefill slot, take, final, the token behind the chunk)``.
        ``spans`` is 2 for a live decode row and 0 for every other (the
        prefill row too). Returns the pool and ONE int32 block ``(3 * ksteps +
        4, num_slots)``: each step's two sampled tokens (step-major), each
        step's advance (0: the row did not step), then each row's next token,
        draft, write head and sampling step; then, collecting, the stack's
        and the module's logits ``(ksteps, 2, num_slots, V)`` (a final chunk's
        row: its last column's in step 0, column 0) and, with routed experts,
        the chunk's and each step's chosen experts, the module's expert layer
        LAST ``((layers, C, k)``, ``(ksteps, layers, num_slots, 2, k))``;
        then the MoE stats."""
        keeps_void = self._draft_keeps_void
        key = ("draft", sampling, collect, width, ksteps) + (("void", ) if keeps_void else ())

        def build():
            model = self.engine.module
            K = ksteps
            tp = self._shard_deg
            stats = self._moe_stats
            choice = collect and self._moe

            sample = _sampler(sampling)

            def stack_and_module(params, pool, ids, pos, heads, spans, next_ids):
                """The stack over ``ids``, then the module over its output and
                what ``next_ids(logits)`` returns first: each column's next
                token, the columns a row the module runs, and whatever else
                it made. Returns (logits f32, draft logits f32, pool, stats,
                choice, that last thing)."""
                lg, pool, *rest = model.apply_with_cache(
                    params, ids, pool, 0, position_ids=pos, write_index=heads, q_spans=spans,
                    expert_stats=stats, expert_choice=choice, with_hidden=True)
                hidden = rest.pop()
                lg = _replicate_logits(lg.astype(jnp.float32), tp)
                nxt, m_spans, made = next_ids(lg)
                dl, pool, *m_rest = model.mtp_forward(
                    params, hidden, nxt, pool, pos, heads, m_spans,
                    expert_stats=stats, expert_choice=choice)
                cnt = (jnp.concatenate([self._moe_forward_stats(rest.pop(0)),
                                        self._moe_forward_stats(m_rest.pop(0))])
                       if stats else None)
                ch = jnp.concatenate([rest.pop(0), m_rest.pop(0)]) if choice else None
                return lg, dl.astype(jnp.float32), pool, cnt, ch, made

            def draft(params, pool, ids, lengths, spans, seeds, steps, flags, temps, topks,
                      topps, *chunk_ops):
                N = ids.shape[0]
                V = model.cfg.vocab_size
                rows = jnp.arange(N)
                sample_at = lambda l2, at: sample(l2, seeds, at, flags, temps, topks, topps)

                def one_step(pool, tok, dr, heads, steps, live):
                    """Verify ``[tok, dr]`` at each live row's write head,
                    decide its advance, run the module over what was
                    committed and draft behind it."""
                    def sampled(lg):
                        s0, s1 = sample_at(lg[:, 0], steps), sample_at(lg[:, 1], steps + 1)
                        acc = live if keeps_void else live & (dr == s0)
                        adv = jnp.where(live, 1 + acc.astype(jnp.int32), 0)
                        return jnp.stack([s0, s1], axis=1), adv, (s0, s1, acc, adv)

                    pos = heads[:, None] + jnp.arange(2)[None, :]
                    lg, dl, pool, cnt, ch, (s0, s1, acc, adv) = stack_and_module(
                        params, pool, jnp.stack([tok, dr], axis=1), pos, heads,
                        jnp.where(live, 2, 0), sampled)
                    last = jnp.maximum(adv - 1, 0)
                    nd = jnp.argmax(dl[rows, last], axis=-1).astype(jnp.int32)
                    tok = jnp.where(live, jnp.where(acc, s1, s0), tok)
                    return (pool, tok, jnp.where(live, nd, dr), heads + adv, steps + adv,
                            jnp.stack([s0, s1]), adv, lg.swapaxes(0, 1), dl.swapaxes(0, 1),
                            cnt, ch)

                live = spans > 0
                out_toks = jnp.zeros((K, 2, N), jnp.int32)
                out_adv = jnp.zeros((K, N), jnp.int32)
                out_lg = jnp.zeros((K, 2, N, V) if collect else (), jnp.float32)
                out_dl = jnp.zeros((K, 2, N, V) if collect else (), jnp.float32)

                def put(outs, k, toks2, adv, lg, dl, ch):
                    out_toks, out_adv, out_lg, out_dl, out_ch = outs
                    upd = jax.lax.dynamic_update_index_in_dim
                    out_toks, out_adv = upd(out_toks, toks2, k, 0), upd(out_adv, adv, k, 0)
                    if collect:
                        out_lg, out_dl = upd(out_lg, lg, k, 0), upd(out_dl, dl, k, 0)
                    if choice:
                        out_ch = upd(out_ch, ch, k, 0)
                    return out_toks, out_adv, out_lg, out_dl, out_ch

                def step(k, carry):
                    pool, tok, dr, heads, steps, live, outs, total = carry
                    pool, tok, dr, heads, steps, toks2, adv, lg, dl, cnt, ch = one_step(
                        pool, tok, dr, heads, steps, live)
                    return (pool, tok, dr, heads, steps, live, put(outs, k, toks2, adv, lg, dl, ch),
                            total + cnt if stats else total)

                # the stack's expert layers and the module's
                n_moe = 1 + sum(model.cfg.layer_parts(i)[1] == "moe"
                                for i in range(model.cfg.num_layers))
                out_ch = (jnp.zeros((K, n_moe, N, 2, model.cfg.moe_top_k), jnp.int32)
                          if choice else ())
                first_ch = jnp.zeros((0, ), jnp.int32)
                tok, dr, heads = ids[:, 0], ids[:, 1], lengths
                outs = (out_toks, out_adv, out_lg, out_dl, out_ch)
                total = jnp.zeros((n_moe, model.cfg.num_experts + 2), jnp.int32)  # a chunk's: below
                k0 = 0
                if width > 2:
                    # a chunk sync: the decode rows' step, then the chunk as a
                    # (1, C) forward over its own slot's rows of the pool
                    ps, take, final, behind = chunk_ops[0]
                    final = final > 0
                    (pool, tok, dr, heads, steps, toks2, adv, lg, dl, total, ch) = one_step(
                        pool, tok, dr, heads, steps, live)
                    start = lengths[ps]
                    last = jnp.maximum(take - 1, 0)
                    row_ids = ids[ps]

                    def closed(lgc):
                        tok0 = sample(lgc[:, last], *(x[ps][None] for x in (
                            seeds, steps, flags, temps, topks, topps)))
                        nxt = jnp.roll(row_ids, -1).at[last].set(jnp.where(final, tok0[0], behind))
                        return nxt[None], take[None], tok0[0]

                    cpos = (start + jnp.arange(width))[None]
                    lgc, dlc, row_pool, cntc, chc, tok0 = stack_and_module(
                        params, slot_slice(pool, ps), row_ids[None], cpos, start[None],
                        take[None], closed)
                    pool = slot_update(pool, ps, row_pool)
                    if stats:
                        total = total + cntc
                    if choice:
                        first_ch = chc[:, 0]  # (layers, C, k)
                    # a final chunk's row joins the steps behind its token 0
                    joins = (rows == ps) & final
                    d0 = jnp.argmax(dlc[0, last], axis=-1).astype(jnp.int32)
                    tok, dr = jnp.where(joins, tok0, tok), jnp.where(joins, d0, dr)
                    heads = jnp.where(joins, start + take, heads)
                    steps = jnp.where(joins, 1, steps)
                    live = live | joins
                    toks2 = toks2.at[0].set(jnp.where(joins, tok0, toks2[0]))
                    adv = jnp.where(joins, 1, adv)
                    if collect:
                        lg = lg.at[0].set(jnp.where(joins[:, None], lgc[0, last][None], lg[0]))
                        dl = dl.at[0].set(jnp.where(joins[:, None], dlc[0, last][None], dl[0]))
                    outs = put(outs, 0, toks2, adv, lg, dl, ch)
                    k0 = 1
                carry = (pool, tok, dr, heads, steps, live, outs, total if stats else ())
                if K > k0:
                    carry = jax.lax.fori_loop(k0, K, step, carry)
                pool, tok, dr, heads, steps, _, outs, total = carry
                out_toks, out_adv, out_lg, out_dl, out_ch = outs
                block = jnp.concatenate([out_toks.reshape(2 * K, N), out_adv,
                                         jnp.stack([tok, dr, heads, steps])])
                return ((pool, block) + ((out_lg, out_dl) if collect else ())
                        + ((first_ch, out_ch) if choice else ())
                        + ((total, ) if stats else ()))

            return self._jit_step(draft, (2 if collect else 0) + (2 if choice else 0)
                                  + (1 if stats else 0) + 1, (1, ))

        return self._program(key, build)
