"""Serving driver: ``ContinuousBatcher.step()`` over ``PagedServer``.

Set-up makes the weights on the device from the seed, builds the server
and scheduler the cell file names, and runs every program the cell's
length bounds can reach once: the fused decode horizon at each pow2
batch and page-table width, and the prefill chunk at each pow2 chunk
length.  A warm request then drives the whole path once, so the eager
operations around the programs are compiled too.

The window offers the mix's requests on their schedule: an open loop
submits each request when it is due (arrivals start ``lead_s`` before
the window, so it opens at steady state), a closed loop submits a
client's next request when its last one finished.  Every request is
timed from its due time.  An open loop then drains the requests due in
the window, for at most ``drain_s``, still offering load.

Afterwards the program's state is freed and the plain reference
(``bench/ref_lm.py``) teacher-forces a sample of the finished requests,
the longest among them: ``served_gap_max`` is the widest gap by which a
served token's logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, ref_lm, session, weights

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"serve driver runs SwiGLU models, got "
                         f"{cfg['hidden_act']!r}")
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm", act="swiglu")


def program_shapes(cell) -> dict:
    """Every program shape the cell's length bounds can reach:
    ``horizon`` (batch, table width) pairs and ``prefill`` (chunk,
    page-row width) pairs, pow2-bucketed as the server buckets them."""
    s, sch, mix = cell.cell["server"], cell.cell["scheduler"], cell.mix
    page, h = s["page_size"], sch["horizon"]
    pmin, pmax = mix["prompt"]["min"], mix["prompt"]["max"]
    omax = mix["output"]["max"]
    chunk = sch["prefill_chunk"]
    prefill = sorted({(_pow2(min(chunk, p - s)),
                       _pow2(-(-min(p, s + chunk) // page)))
                      for p in range(pmin, pmax + 1)
                      for s in range(0, p, chunk)})
    lo = _pow2(-(-(pmin + h) // page))
    hi = _pow2(-(-(pmax + omax + h) // page))
    widths = [w for w in (1 << i for i in range(20)) if lo <= w <= hi]
    batches = [b for b in (1 << i for i in range(20))
               if b <= _pow2(sch["max_active"])]
    return {"horizon": [(b, w) for b in batches for w in widths],
            "prefill": prefill}


def warm(server, cell) -> int:
    """Run each reachable program once on padding inputs (lengths 0:
    every append is dropped, every attention row is empty).  Returns the
    number of programs."""
    shapes = program_shapes(cell)
    h = cell.cell["scheduler"]["horizon"]
    key = jax.random.PRNGKey(0)
    for b, w in shapes["horizon"]:
        z = jnp.zeros((b,), jnp.int32)
        _, _, state = server._horizon_jit(
            server.params, server.store.device_state(),
            jnp.zeros((b, w), jnp.int32), z, z, z,
            jnp.asarray(np.int32(-1)), key, jnp.float32(0.0),
            jnp.float32(1.0), z, horizon=h)
        server.store.adopt(state)
    for c, w in shapes["prefill"]:
        _, state = server._chunk_jit(
            server.params, server.store.device_state(),
            jnp.zeros((w,), jnp.int32), jnp.zeros((1, c), jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
        server.store.adopt(state)
    jax.block_until_ready(server.store.device_state())
    return len(shapes["horizon"]) + len(shapes["prefill"])


class Stamps:
    """Harness wrappers around the scheduler's and server's calls: when
    each admission began, each first token, each horizon (with every
    row's length and tokens emitted) and each prefill chunk."""

    def __init__(self, batcher, server):
        self.begin = {}
        self.firsts = 0
        self.horizons = []     # (t0, t1, [(length, emitted), ...])
        self.chunks = []       # (t0, t1, tokens computed)
        begin, activate = batcher._begin_prefill, batcher._activate
        horizon, chunk = server.horizon_batch, server.prefill_chunk

        def _begin(req):
            self.begin[req.rid] = time.monotonic()
            return begin(req)

        def _activate(req, last):
            self.firsts += 1
            return activate(req, last)

        def _horizon(tokens, budgets, *a, **kw):
            lens = [server.table.length(s) for s in tokens]
            t0 = time.monotonic()
            with session.span("server.horizon_batch"):
                out = horizon(tokens, budgets, *a, **kw)
            self.horizons.append((t0, time.monotonic(), [
                (n, len(out[s])) for n, s in zip(lens, tokens)]))
            return out

        def _chunk(seq_id, c=None):
            before = server.prefill_tokens_computed
            t0 = time.monotonic()
            with session.span("server.prefill_chunk"):
                out = chunk(seq_id, c)
            self.chunks.append((t0, time.monotonic(),
                                server.prefill_tokens_computed - before))
            return out

        batcher._begin_prefill = _begin
        batcher._activate = _activate
        server.horizon_batch = _horizon
        server.prefill_chunk = _chunk


def _request(rid, prompt, max_tokens):
    from repro.runtime.scheduler import Request
    return Request(rid, prompt, max_tokens, eos_id=None)


def drive(batcher, stamps, items, prompts, *, closed: bool, lead: float,
          seconds: float, drain_s: float, tracer):
    """Offer ``items`` and step the scheduler.  Returns the record of
    the window: requests, steps, lateness, window bounds."""
    reqs = {}
    late = []
    steps = []
    t0 = time.monotonic()
    ws, we = t0 + lead, t0 + lead + seconds
    due_abs = {}

    def submit(it, due):
        now = time.monotonic()
        r = _request(it.rid, prompts[it.rid], it.max_tokens)
        batcher.submit(r)
        r.t_arrive = due               # timed from when it was due
        due_abs[it.rid] = due
        reqs[it.rid] = r
        late.append(now - due)

    if closed:
        queues = {}
        for it in items:
            queues.setdefault(it.client, deque()).append(it)
        for q in queues.values():
            submit(q.popleft(), t0)
        client_of = {it.rid: it.client for it in items}
        n_done = 0
    else:
        arrivals = deque(sorted(items, key=lambda it: it.due))
    while True:
        now = time.monotonic()
        if closed:
            for r in batcher.finished[n_done:]:
                q = queues[client_of[r.rid]]
                if q:
                    submit(q.popleft(), r.t_done)
            n_done = len(batcher.finished)
            if now >= we:
                break
        else:
            while arrivals and t0 + arrivals[0].due <= now:
                it = arrivals.popleft()
                submit(it, t0 + it.due)
            if now >= we:
                shed = {r.rid for r in batcher.rejected}
                waiting = [i for i, r in reqs.items()
                           if ws <= due_abs[i] < we and not r.t_done
                           and i not in shed]
                if not waiting or now >= we + drain_s:
                    break
        if tracer is not None:
            tracer.poll(now)
        if not (batcher.waiting or batcher.prefilling or batcher.active):
            nxt = [we + (0 if closed else drain_s)]
            if not closed and arrivals:
                nxt.append(t0 + arrivals[0].due)
            if tracer is not None and tracer.next_event is not None:
                nxt.append(tracer.next_event)
            with session.span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt) - time.monotonic()))
            continue
        f0 = stamps.firsts
        ta = time.monotonic()
        with session.span("scheduler.step"):
            n = batcher.step()
        steps.append((ta, time.monotonic(), n + stamps.firsts - f0))
    if tracer is not None:
        tracer.stop()
    return {"t0": t0, "ws": ws, "we": we, "reqs": reqs, "due": due_abs,
            "late": late, "steps": steps, "t_end": time.monotonic()}


def sample(finished, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest first, as
    (prompt, served tokens) pairs."""
    order = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.output)))
    rng = np.random.default_rng(gen.seed_streams(seed)[3])
    rest = [order[i] for i in rng.permutation(len(order) - 1) + 1] \
        if len(order) > 1 else []
    return [(np.asarray(r.prompt, np.int32), np.asarray(r.output, np.int32))
            for r in order[:1] + rest[:max(0, n - 1)]]


class Served:
    """The program under test, set up: weights from the seed, the
    server and scheduler of the cell file, every reachable program run
    once and a warm request served.  ``setup_s`` is what that took."""

    def __init__(self, cell, seed: int, log):
        from repro.models.api import get_model
        from repro.runtime.scheduler import ContinuousBatcher
        from repro.runtime.serve import PagedServer

        cfg, c = cell.config, cell.cell
        self.cell = cell
        self.counter = session.CompileCounter()
        t0 = time.monotonic()
        self.dtype = DTYPES[c["server"]["dtype"]]
        self.model = get_model(arch_config(cfg), compute_dtype=self.dtype)
        params = self.make_weights(seed)
        self.server = PagedServer(self.model, params,
                                  page_size=c["server"]["page_size"],
                                  hbm_pages=c["server"]["hbm_pages"],
                                  dtype=self.dtype)
        self.batcher = ContinuousBatcher(self.server, **c["scheduler"])
        self.n_programs = warm(self.server, cell)
        self.stamps = Stamps(self.batcher, self.server)
        # one request through the whole path (its prefill and one
        # horizon): the eager operations around the programs compile
        # here, not in the window
        req = _request(-1, np.arange(cell.mix["prompt"]["min"],
                                     dtype=np.int32),
                       1 + c["scheduler"]["horizon"])
        self.batcher.submit(req)
        while not req.done:
            self.batcher.step()
        self.reset()
        self.setup_s = time.monotonic() - t0
        self.setup_counts = self.counter.snapshot()
        log(f"setup {self.setup_s!r} s: weights "
            f"{sum(x.nbytes for x in jax.tree.leaves(params))} B, "
            f"{self.n_programs} programs warmed, {self.setup_counts}")

    def make_weights(self, seed: int):
        """Weights of ``seed``, checked against the program's layout."""
        self.wseed = gen.jax_seed(gen.seed_streams(seed)[2])
        params = weights.make(self.cell.config, self.wseed, self.dtype)
        want = jax.eval_shape(lambda k: self.model.init(k, dtype=self.dtype),
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise ValueError("bench/weights.py layout differs from the "
                             "program's parameters")
        return jax.block_until_ready(params)

    def close(self) -> None:
        """Drop the program's device state: weights and pages."""
        self.server.params = None
        self.server.store = None
        self.batcher = self.server = self.stamps = None
        gc.collect()

    def reset(self) -> None:
        """Free every sequence and forget every request and stamp."""
        b, s = self.batcher, self.server
        for rid in list(b.active) + list(b.prefilling):
            s.free_sequence(rid)
        b.waiting.clear()
        b.prefilling.clear()
        b.active.clear()
        b.finished.clear()
        b.rejected.clear()
        s.table.clear_prefix_cache()
        self.stamps.begin.clear()
        self.stamps.horizons.clear()
        self.stamps.chunks.clear()


def measure(S: Served, mix: dict, seed: int, seconds: float, *, lead: float,
            drain: float, trace_s=None, log) -> dict:
    """Offer ``mix`` for ``seed`` and time the window.  Returns the
    window's record; ``finished`` holds the finished requests."""
    cfg = S.cell.config
    closed = mix["arrivals"]["kind"] == "closed"
    items = gen.schedule(mix, seed, (lead, seconds, drain))
    prompts = gen.token_ids(seed, items, cfg["vocab_size"])
    tracer = None
    if trace_s:
        tspan = min(trace_s, seconds)
        tracer = session.Tracer(time.monotonic() + lead +
                                (seconds - tspan) / 2, tspan)
    before = S.counter.snapshot()
    w = drive(S.batcher, S.stamps, items, prompts, closed=closed, lead=lead,
              seconds=seconds, drain_s=drain, tracer=tracer)
    in_window = {k: v - before[k] for k, v in S.counter.snapshot().items()}
    b = S.batcher
    late = np.asarray(w["late"])
    log(f"window {seconds} s after {lead} s lead: {len(w['reqs'])} requests"
        f" submitted, {len(b.finished)} finished, {len(b.rejected)} "
        f"rejected, {len(b.waiting)} waiting at the end; generator late p50 "
        f"{float(np.median(late)) * 1e3!r} ms, max "
        f"{float(late.max()) * 1e3!r} ms; traced/compiled in window "
        f"{in_window}")
    ws, we = w["ws"], w["we"]
    reqs = []
    for rid, r in w["reqs"].items():
        due = w["due"][rid]
        counted = (ws <= r.t_done < we) if closed else (ws <= due < we)
        reqs.append({"rid": rid, "due": due,
                     "begin": S.stamps.begin.get(rid),
                     "first": r.t_first or None, "done": r.t_done or None,
                     "n_out": len(r.output), "prompt_len": len(r.prompt),
                     "counted": bool(counted)})
    n_counted = sum(q["counted"] for q in reqs)
    log(f"counted requests: {n_counted} "
        f"({'finished in' if closed else 'due in'} the window)")
    if closed:
        attempted = n_counted + len(b.active) + len(b.prefilling) + \
            len(b.waiting)
        unanswered = 0
        failed = len(b.rejected)
    else:
        counted = [q for q in reqs if q["counted"]]
        attempted = len(counted)
        unanswered = sum(q["done"] is None for q in counted)
        failed = unanswered
    rec = {"window": (ws, we), "t_end": w["t_end"], "seconds": seconds,
           "requests": reqs, "steps": w["steps"],
           "horizons": list(S.stamps.horizons),
           "chunks": list(S.stamps.chunks), "compiles_in_window": in_window,
           "attempted": attempted, "failed": failed,
           "unanswered": unanswered, "waiting_at_end": len(b.waiting),
           "config": cfg, "horizon": S.cell.cell["scheduler"]["horizon"],
           "finished": list(b.finished)}
    if tracer is not None:
        rec["trace"] = tracer.reduce(rec)
        rec["trace_window"] = (tracer.t_start, tracer.t_stop)
    return rec


def check(cell, pairs, wseed: int, dtype, log) -> dict:
    """The reference over the sampled (prompt, served) ``pairs``."""
    t0 = time.monotonic()
    params = weights.make(cell.config, wseed, dtype)
    gaps = [ref_lm.served_gaps(cell.config, params, p, o) for p, o in pairs]
    gap = float(max(g.max() for g in gaps)) if gaps else None
    n_tok = int(sum(len(o) for _, o in pairs))
    log(f"reference over {len(pairs)} requests, {n_tok} served tokens in "
        f"{time.monotonic() - t0!r} s: widest gap {gap!r}")
    return {"served_gap_max": gap, "checked_tokens": n_tok}


def run(cell, *, seed: int, seconds: float, trace: bool, log) -> dict:
    c = cell.cell
    S = Served(cell, seed, log)
    rec = measure(S, cell.mix, seed, seconds, lead=c["lead_s"],
                  drain=c["drain_s"], trace_s=c["trace_s"] if trace else None,
                  log=log)
    rec["setup_s"] = S.setup_s
    rec["memory_peak_bytes"] = session.memory_peak()
    pairs = sample(rec.pop("finished"), c["check"]["requests"], seed)
    wseed, dtype = S.wseed, S.dtype
    S.close()
    log(f"device bytes in use after freeing the program: "
        f"{session.bytes_in_use()}")
    got = check(cell, pairs, wseed, dtype, log)
    lim = c["check"]["limits"]
    rec["checks"] = {
        "served_gap_max": {"value": got["served_gap_max"],
                           "limit": lim["served_gap_max"]},
        "requests_unanswered": {"value": rec["unanswered"], "limit": 0}}
    rec["checked_tokens"] = got["checked_tokens"]
    rec["checked_pairs"], rec["wseed"] = pairs, wseed
    return rec
