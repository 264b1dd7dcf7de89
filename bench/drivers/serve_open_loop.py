"""Open-loop serving of a model through ``serve.engine.Engine``.

Set-up builds the benchmark's own bf16 weights on the device from the
seed (``bench/configs/<config>.ref.py``), the engine, and, where the
traffic shares golden prompts, registers them. It then warms every shape
the window will use: each prompt or suffix length, every decode batch
bucket, the KV index at its steady size (by admitting the cap of
concurrent sequences and freeing them) and the index stamps of every
width a step can need.

The window offers requests on a Poisson schedule at the traffic's fixed
rate. The schedule (arrival gaps, lengths, outputs) is the same for
every seed; the seed draws the weights and the token ids. At most ``max_running`` sequences decode at
once; later arrivals wait in FIFO order and the wait counts.

Reported: ``ttft_p90_ms`` over all requests due in the window, from
arrival to the first token on the host (one unserved at the close enters
with the time it has waited), and ``itl_p99_ms`` over every gap between
consecutive tokens of one request. Once the window has closed and the
engine is freed, a sample of finished requests drawn from the seed, the
longest among them, is compared with the plain reference: the widest gap
by which a served (greedy) token's logit lies below the reference's best.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import numpy as np

from bench import harness, work
from repro.configs import get_config
from repro.core import fleet as fleet_lib
from repro.serve.engine import Engine

#: Seed of the request schedule (arrival gaps, lengths, outputs), the same
#: for every run: with a few dozen requests in a window, the order of the
#: long admissions decides the tails, and a seed that reordered them moved
#: ``ttft_p90_ms`` by up to 35% between seeds (2% between runs of one).
SCHEDULE_SEED = 20240707
#: Sample size of the comparison: requests until this many served tokens.
SAMPLE_TOKENS = 320
SAMPLE_MAX_REQUESTS = 8
#: The reference's fixed shape: tokens per sequence, served per sequence.
REF_TOKENS = 1792
REF_SERVED = 128


def program_config(cfg: dict):
    """The program's own config of this model, checked against the
    benchmark's numbers."""
    mc = get_config(cfg["program_config"])
    want = dict(n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
                hd=cfg["head_dim"], norm_eps=cfg["rms_norm_eps"],
                rope_theta=cfg["rope_theta"],
                tie_embeddings=cfg["tie_word_embeddings"])
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg['program_config']!r} differs "
                         f"from {cfg['name']}: {got} != {want}")
    return mc


@dataclasses.dataclass
class Request:
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    out_len: int            # tokens to serve (greedy, no EOS)
    first: float | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list | None = None
    done: bool = False


def _counts(weights, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` items over ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    c = np.floor(exact).astype(int)
    c[np.argsort(-(exact - c))[: n - c.sum()]] += 1
    return c


def make_requests(tr: dict, vocab: int, rng: np.random.Generator,
                  goldens: list[np.ndarray], n: int) -> list[Request]:
    """``n`` requests on one schedule for every seed: arrival gaps at the
    exponential's quantiles, lengths split by their weights and outputs
    at the uniform's quantiles, in an order fixed by ``SCHEDULE_SEED``.
    ``rng`` (the run's seed) draws the token ids."""
    order = np.random.default_rng(SCHEDULE_SEED)
    q = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-q) / tr["rate_per_s"])
    arrivals = np.cumsum(gaps) - gaps[0]
    lo, hi = tr["output_len_min"], tr["output_len_max"]
    outs = order.permutation(lo + np.floor(q * (hi - lo + 1)).astype(int))
    suffix = order.permutation(np.repeat(tr["suffix_lens"],
                                         _counts(tr["suffix_weights"], n)))
    if goldens:
        gw = [1.0 / (k + 1) for k in range(len(goldens))]
        which = order.permutation(np.repeat(np.arange(len(goldens)),
                                            _counts(gw, n)))
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, suffix[i], dtype=np.int32)
        head = (goldens[which[i]] if goldens else
                rng.integers(0, vocab, tr["head_len"], dtype=np.int32))
        reqs.append(Request(float(arrivals[i]),
                            np.concatenate([head, tail]).astype(np.int32),
                            int(outs[i])))
    return reqs


@dataclasses.dataclass
class State:
    weights: dict
    engine: Engine
    goldens: list


def setup(ctx) -> State:
    """Weights, engine, golden prompts and the warm-up of every shape."""
    cfg, tr = ctx.config, ctx.traffic
    mc = program_config(cfg)
    weights = jax.block_until_ready(
        ctx.cell.reference().init_weights(cfg, ctx.seed))
    eng = Engine(mc, weights, **cfg["engine"])
    if eng.decode_path != tr["decode_path"]:
        raise ValueError(f"engine took decode path {eng.decode_path!r}, "
                         f"the traffic states {tr['decode_path']!r}")
    rng = np.random.default_rng([ctx.seed % (1 << 64), 21])
    goldens = [rng.integers(0, cfg["vocab_size"], tr["head_len"],
                            dtype=np.int32)
               for _ in range(tr.get("golden_prompts", 0))]
    for g in goldens:
        eng.register_golden(g)
    warm_up(eng, tr, cfg["vocab_size"], goldens,
            np.random.default_rng([ctx.seed % (1 << 64), 22]))
    return State(weights=weights, engine=eng, goldens=goldens)


def warm_up(eng: Engine, tr: dict, vocab: int, goldens, rng) -> None:
    """Admit ``max_running`` requests, every length the traffic uses among
    them (growing the KV index to its steady size), decode through every
    batch bucket, free them all, and compile the index stamp at every
    width."""
    cap = tr["max_running"]
    lens = list(tr["suffix_lens"])
    sids = []
    for i in range(cap):
        # each length once, then the shortest: the KV index needs the
        # sequences, not their lengths
        suffix = lens[i] if i < len(lens) else min(lens)
        head = (goldens[i % len(goldens)] if goldens else
                rng.integers(0, vocab, tr["head_len"], dtype=np.int32))
        tail = rng.integers(0, vocab, suffix, dtype=np.int32)
        sids.append(eng.add_request(np.concatenate([head, tail])))
    b = cap
    while b >= 1:
        while len(sids) > b:
            eng.finish_request(sids.pop())
        for _ in range(2):
            eng.step()
        b //= 2
    for sid in sids:
        eng.finish_request(sid)
    fl = eng.kv.fleet
    k = 1
    while k <= 2 * cap:
        # a no-op stamp (every tenant id is the drop sentinel) at width k
        drop = np.full(k, fl.spec.n_tenants, np.int32)
        zero = np.zeros(k, np.int32)
        jax.block_until_ready(fleet_lib.stamp_entries(
            fl, drop, zero, zero, np.zeros((k, 2), np.uint32)).l2)
        k *= 2


def serve_window(ctx, state: State, reqs: list[Request]) -> dict:
    """Offer ``reqs`` on their schedule for the window; returns the
    bookkeeping the metrics need."""
    eng, tr = state.engine, ctx.traffic
    cap = tr["max_running"]
    queue: collections.deque = collections.deque()
    running: dict[int, Request] = {}
    steps: list[list[int]] = []     # per decode step: each row's context
    depth: list[tuple[float, int]] = []   # (time into the window, queued)
    nxt = 0
    prep = eng.kv.prepare_step_fused

    def traced_prep(*a, **kw):
        with ctx.span("prep"):
            return prep(*a, **kw)

    eng.kv.prepare_step_fused = traced_prep
    try:
        with ctx.window() as win:
            t0 = ctx.window_start
            end = t0 + ctx.seconds
            while win.open():
                now = time.perf_counter()
                while nxt < len(reqs) and t0 + reqs[nxt].arrival <= now:
                    queue.append(reqs[nxt])
                    nxt += 1
                depth.append((now - t0, len(queue)))
                while queue and len(running) < cap and win.open():
                    r = queue.popleft()
                    with ctx.span("admit"):
                        sid = eng.add_request(r.prompt)
                    r.first = time.perf_counter()
                    r.times.append(r.first)
                    running[sid] = r
                if running:
                    ctxs = [r.prompt.size + len(r.times)
                            for r in running.values()]
                    with ctx.span("step"):
                        eng.step()
                    t = time.perf_counter()
                    steps.append(ctxs)
                    for sid in list(running):
                        r = running[sid]
                        r.times.append(t)
                        if len(r.times) >= r.out_len:
                            r.tokens = list(eng.active[sid])
                            r.done = True
                            eng.finish_request(sid)
                            del running[sid]
                elif nxt < len(reqs):
                    wait = min(t0 + reqs[nxt].arrival, end) - now
                    if wait > 0:
                        with ctx.span("wait"):
                            time.sleep(wait)
                else:
                    with ctx.span("wait"):
                        time.sleep(max(0.0, end - now))
            close = time.perf_counter()
    finally:
        eng.kv.prepare_step_fused = prep
    return dict(close=close, steps=steps, depth=depth,
                left=len(queue) + len(running))


def choose_sample(reqs: list[Request], rng) -> list[Request]:
    """Finished requests to compare: the longest, then others drawn from
    the seed until ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].prompt.size + done[i].out_len)
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, served = [], 0
    for i in order:
        if served >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(done[i])
        served += done[i].out_len
    return out


def compare(ctx, state: State, sample: list[Request], *,
            control: bool = False):
    """Free the engine, then run the reference over each sampled request.
    Returns the served tokens' gaps and, with ``control``, the gaps of the
    tokens a float8 reference would have served at the same positions."""
    state.engine = None
    ref = ctx.cell.reference()
    cfg = ctx.config
    return ref.served_gaps(
        ref.make_logits_fn(cfg), state.weights,
        [(r.prompt, np.asarray(r.tokens[:r.out_len], np.int32))
         for r in sample], pad_to=REF_TOKENS, k_max=REF_SERVED,
        control_fn=ref.make_logits_fn(cfg, quantized=True) if control else None)


def requests_for(ctx, state: State) -> tuple[list[Request], np.random.Generator]:
    """The run's requests, enough for the window, and the seeded generator
    that draws the comparison's sample afterwards."""
    tr = ctx.traffic
    rng = np.random.default_rng([ctx.seed % (1 << 64), 23])
    n = int(np.ceil(tr["rate_per_s"] * ctx.seconds * 1.25)) + 1
    return make_requests(tr, ctx.config["vocab_size"], rng, state.goldens,
                         n), rng


def run(ctx) -> harness.Outcome:
    cfg = ctx.config
    state = setup(ctx)
    reqs, rng = requests_for(ctx, state)
    book = serve_window(ctx, state, reqs)
    t0, end = ctx.window_start, ctx.window_start + ctx.seconds
    peak = harness.memory_peak_bytes(jax.devices()[:ctx.cell.entry["chips"]])

    due = [r for r in reqs if r.arrival <= ctx.seconds]
    ttft = [(r.first if r.first is not None else end) - (t0 + r.arrival)
            for r in due]
    itl = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])]
    sample = choose_sample(reqs, rng)
    gaps, _ = compare(ctx, state, sample)
    widest = float(gaps.max()) if gaps.size else float("inf")
    steps = book["steps"]
    return harness.Outcome(
        end_to_end=dict(ttft_p90_ms=1e3 * float(np.quantile(ttft, 0.90)),
                        itl_p99_ms=1e3 * float(np.quantile(itl, 0.99)),
                        setup_s=ctx.setup_s()),
        attempted=len(due), failed=0,
        checks=[harness.Check("served_logit_gap", widest,
                              cfg["served_logit_gap_limit"])],
        memory_peak_bytes=peak,
        layer=dict(
            decode_steps=len(steps),
            decode_flops=sum(work.decode_flops(cfg, c) for c in steps),
            decode_kv_bytes=sum(work.decode_kv_bytes(cfg, c) for c in steps),
            admissions=sum(r.first is not None for r in reqs)),
        notes=[f"serve: {len(due)} requests due, "
               f"{sum(r.first is not None for r in due)} admitted, "
               f"{sum(r.done for r in reqs)} finished, {book['left']} queued "
               f"or running at the close; {len(steps)} decode steps, mean "
               f"batch {np.mean([len(s) for s in steps]) if steps else 0:.2f}"
               f"; compared {len(sample)} requests, {gaps.size} served "
               f"tokens, widest gap {widest:.5f}"],
    )
