"""The comparison that decides ``correct``: what the timed path served
against the plain reference that the configuration names.

A configuration file's ``reference`` key names a file,
``chipbench/references/<name>.py``, that binds ``Reference``
(``run.reference_of`` loads it).  A ``Reference`` keeps this contract:

  * ``Reference(conf, key, *, control=None)``: ``conf`` is the
    configuration file, ``key`` the PRNG key of the run's seed, from which
    it makes its own weights; ``control`` ("fp8", "int8") computes every
    weight matmul one precision below the configuration's;
  * ``routing()``: per token id, the class its router picks (0 the exact
    FFN, c >= 1 approximator c), the other of its two best classes, and
    whether those two tie within the program's rounding: three arrays
    over the vocabulary, as ``replay`` reads them;
  * ``hidden(tokens, modes)``: the final normed hidden states (R, L, d) of
    token rows (R, L), given per position the FFN each ran: ``EXACT``,
    c >= 1 approximator c, or ``DROPPED`` (none, over capacity);
  * ``logit_stats(h, ids)``: per position of ``hidden``'s output, the best
    logit, the logit of ``ids`` (R, L), and the id the logits put first.

After the window, a sample of the finished requests, drawn from the seed
and holding the one with the most served tokens, is run through the
reference once, prompt and served tokens together.  At each position
that produced a served token the reference's best logit is compared
with its logit for the token the program served; the widest such gap
over the sample is the number compared (``max_logit_gap``).  With greedy
decoding a sound program serves the reference's best token or one whose
logit lies within rounding of it.

The MCMA layer sends each row of a tick to one FFN, and a row over its
class's capacity gets none, so what a position computed depends on the
other rows of its tick.  ``replay`` rebuilds every tick from the rows the
recorder saw (which request sat in which row, and its tokens), routes
each row with the reference's own router and applies the configuration's
capacities in row order, as the configuration states them.  The
per-class counts it finds are compared with the step's own counts, tick
by tick.  Where they differ only because a row's token has router logits
tied within bfloat16 rounding (the program's rounding may then pick
either class), the tick is replayed with such rows moved to their other
class and its requests are left out of the sample: the reference cannot
say which FFN those rows ran.  Differences that no tie explains are
counted (``plan_mismatch_ticks``).

Every finished request must also have served exactly ``max_new`` tokens,
each a valid id (``bad_requests``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

EXACT, DROPPED = 0, -1          # FFN modes beside approximator c >= 1


def capacity(rows: int, frac: float) -> int:
    """Rows a class may run in a tick of ``rows`` rows."""
    return max(min(int(rows * frac), rows), 1)


def tick_rows(t):
    """(row ids in order, their tokens, their request's row index) of the
    active rows of a recorded tick."""
    if t.phase == "decode":
        b = np.nonzero(t.rows)[0]
        return b, t.tokens[b, 0], np.zeros_like(b)
    s = t.tokens.shape[1]
    active = np.arange(s)[None, :] < t.rows[:, None]
    b, j = np.nonzero(active)
    return b, t.tokens[b, j], j


def plan(cls: np.ndarray, rows: int, n: int, approx: dict):
    """Per active row: its FFN (0 exact, c approximator c, -1 none) under
    the capacities, given classes in row order; and the per-class routed
    and executed counts."""
    caps = [capacity(rows, approx["exact_frac"])] + \
        [capacity(rows, approx["invoke_frac"])] * n
    mode = np.full(cls.shape, DROPPED, np.int64)
    counts = np.zeros(n + 1, np.int64)
    kept = np.zeros(n + 1, np.int64)
    for c in range(n + 1):
        mine = cls == c
        rank = np.cumsum(mine) - 1
        keep = mine & (rank < caps[c])
        mode[keep] = c
        counts[c], kept[c] = mine.sum(), keep.sum()
    return mode, counts, kept


def explain(cls, toks, near, alt, counts, target):
    """Move rows whose token's router logits tie within rounding to their
    other top class, one by one, while that brings the routed counts
    toward ``target``.  Returns the new classes, or None where the
    counts cannot be reached so."""
    cls, delta = cls.copy(), np.rint(target).astype(np.int64) - counts
    for k in np.nonzero(near[toks])[0]:
        a, b = cls[k], alt[toks[k]]
        if b != a and delta[a] < 0 and delta[b] > 0:
            cls[k] = b
            delta[a] += 1
            delta[b] -= 1
    return cls if not delta.any() else None


@dataclasses.dataclass
class Replay:
    """Every tick rebuilt: per tick the active rows' batch row, token,
    offset in the chunk and FFN, and the request ids the reference cannot
    pin down (a tick whose counts only a tie within rounding explains)."""

    rows: list            # per tick (b, tokens, j, mode)
    ambiguous: set        # request ids with a row in such a tick
    ambiguous_ticks: int
    mismatches: int       # ticks whose counts nothing explains


def replay(ticks, routing, approx: dict) -> Replay:
    """Route every recorded tick with the reference's router and apply the
    capacities in row order; compare the counts with the step's own."""
    table, alt, near = routing
    n = approx["n_approx"]
    out, ambiguous, n_amb, mismatches = [], set(), 0, 0
    for t in ticks:
        b, toks, j = tick_rows(t)
        size = t.tokens.shape[0] * t.tokens.shape[1]
        cls = table[toks]
        mode, counts, kept = plan(cls, size, n, approx)
        ok = lambda c, k: (np.array_equal(c, np.rint(t.counts))
                           and np.array_equal(k, np.rint(t.dispatched)))
        if not ok(counts, kept):
            moved = explain(cls, toks, near, alt, counts, t.counts)
            if moved is not None and ok(*plan(moved, size, n, approx)[1:]):
                mode = plan(moved, size, n, approx)[0]
                ambiguous.update(t.rids[i] for i in set(b.tolist()))
                n_amb += 1
            else:
                mismatches += 1
        out.append((b, toks, j, mode))
    return Replay(out, ambiguous, n_amb, mismatches)


def start_positions(ticks) -> list[np.ndarray]:
    """Per tick, each batch row's position before the tick: how many of
    its request's tokens the cache already held.  The walk follows each
    request from its first tick, so it must start at the server's first
    tick."""
    pos: dict[int, int] = {}
    out = []
    for t in ticks:
        p = np.array([pos.get(r, 0) for r in t.rids], np.int64)
        adv = np.asarray(t.rows, np.int64)    # a decode mask counts 1
        for i in np.nonzero(adv)[0]:
            pos[t.rids[i]] = int(p[i] + adv[i])
        out.append(p)
    return out


def request_rows(ticks, rep: Replay, want: set) -> dict:
    """(position, token, FFN) of every row each wanted request ran."""
    rows_of = {r: [] for r in want}
    for t, p, (b, toks, j, mode) in zip(ticks, start_positions(ticks),
                                        rep.rows):
        rids = np.asarray(t.rids)[b]
        for k in np.nonzero(np.isin(rids, list(want)))[0]:
            rows_of[int(rids[k])].append((int(p[b[k]] + j[k]), int(toks[k]),
                                          int(mode[k])))
    return rows_of


def sample(stamps, seed: int, k: int, skip: set):
    """``k`` finished requests drawn from the seed, the one with the most
    served tokens among them; requests in ``skip`` are left out."""
    done = sorted((st for st in stamps if st.finished and st.item.rid >= 0
                   and st.item.rid not in skip), key=lambda st: st.item.rid)
    if not done:
        return []
    longest = max(done, key=lambda st: (len(st.req.out), -st.item.rid))
    rest = [st for st in done if st is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(chosen, rows_of, max_len: int, pad: int = 256):
    """Token rows (prompt + served tokens but the last), FFN modes, and
    per position the served token it produced (-1 where none).  Rows are
    as long as the longest, rounded up to ``pad`` (a causal model's later
    positions change nothing before them).  A request whose replayed rows
    do not match its tokens position by position is left out and
    counted."""
    r = len(chosen)
    longest = max((len(st.req.prompt) + len(st.req.out) for st in chosen),
                  default=1)
    length = min(max_len, -(-longest // pad) * pad)
    tokens = np.zeros((r, length), np.int32)
    modes = np.zeros((r, length), np.int32)
    served = np.full((r, length), -1, np.int32)
    bad = 0
    for i, st in enumerate(chosen):
        prompt, out = st.req.prompt, st.req.out
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        got = sorted(rows_of[st.item.rid])
        if [p for p, _, _ in got] != list(range(len(seq))) \
                or [t for _, t, _ in got] != seq.tolist():
            bad += 1
            continue
        tokens[i, :len(seq)] = seq
        modes[i, :len(seq)] = [m for _, _, m in got]
        served[i, len(prompt) - 1:len(seq)] = out
    return tokens, modes, served, bad


def bad_requests(stamps, vocab: int) -> int:
    bad = 0
    for st in stamps:
        if st.finished and st.item.rid >= 0:
            out = st.req.out
            bad += len(out) != st.item.max_new or \
                not all(0 <= t < vocab for t in out)
    return bad


def verdict(numbers: dict) -> bool:
    """``correct``: every number at or under its limit, and at least the
    limit's count of tokens checked."""
    return all(v["value"] >= v["limit"] if k == "checked_tokens_at_least"
               else v["value"] <= v["limit"] for k, v in numbers.items())


def check_served(conf: dict, spec: dict, seed: int, served, *,
                 controls: tuple = ()) -> dict:
    """Compare a run with the reference; returns ``{"correct": bool,
    "numbers": {name: {"value", "limit"}}}``.

    Each of ``controls`` ("fp8": the reference with its weight matmuls in
    float8, one precision below the configuration's bfloat16; "int8")
    is put in the program's place on the same sample: the ids it puts
    first at each position stand for the served tokens, and the same
    numbers go through the same ``verdict``.  Its result is under
    ``"controls"``; the benchmark's runs never compute it."""
    from chipbench.run import model_key, reference_of
    reference = reference_of(conf)
    vocab, max_len = conf["model"]["vocab"], conf["serve"]["max_len"]
    ref = reference(conf, model_key(seed))
    rep = replay(served.ticks, ref.routing(), conf["approx"])
    chosen = sample(served.stamps, seed, int(spec["check_requests"]),
                    rep.ambiguous)
    rows_of = request_rows(served.ticks, rep, {st.item.rid for st in chosen})
    tokens, modes, want, unmatched = sequences(chosen, rows_of, max_len)
    served_any = bool((want >= 0).any())
    h = ref.hidden(tokens, modes) if served_any else None
    limit = conf["check"]["max_logit_gap"]

    def numbers_for(ids) -> dict:
        gap = widest_gap(ref, h, ids, want) if served_any else float("inf")
        return {
            "bad_requests": {"value": bad_requests(served.stamps, vocab),
                             "limit": 0},
            "plan_mismatch_ticks": {"value": rep.mismatches, "limit": 0},
            "unreplayed_requests": {"value": unmatched, "limit": 0},
            "checked_tokens_at_least": {"value": int((want >= 0).sum()),
                                        "limit": 1},
            "max_logit_gap": {"value": gap, "limit": limit}}

    numbers = numbers_for(want)
    out = {"correct": verdict(numbers), "numbers": numbers, "controls": {}}
    for kind in controls:
        ids = want
        if served_any:
            ctl = reference(conf, model_key(seed), control=kind)
            _, _, first = ctl.logit_stats(ctl.hidden(tokens, modes),
                                          np.maximum(want, 0))
            ids = np.asarray(first)
        nums = numbers_for(ids)
        out["controls"][kind] = {"correct": verdict(nums), "numbers": nums}
    print(f"[check] {len(chosen)} requests compared, "
          f"{numbers['checked_tokens_at_least']['value']} served tokens; "
          f"{rep.ambiguous_ticks} ticks whose counts a router tie within "
          f"rounding explains ({len(rep.ambiguous)} requests left out of "
          "the sample)")
    return out


def widest_gap(ref, h, ids: np.ndarray, want: np.ndarray):
    """Widest gap between the reference's best logit and its logit for
    ``ids``, over the positions that served a token (``want`` >= 0)."""
    best, got, _ = ref.logit_stats(h, np.maximum(ids, 0))
    best, got = np.asarray(best), np.asarray(got)
    return float(np.max((best - got)[want >= 0]))
