"""Whether the timed path answered correctly: what the window produced,
against the plain float32 reference that each role's configuration names.

Every number is compared with the limit the configuration file gives it:

* ``queries_failed``: queries sent in the window that never came back done.
* ``oracle_logprob_gap`` / ``proxy_logprob_gap`` (nats): on a sample of the
  rows each model scored, drawn from the seed with the longest prompt in it,
  the widest difference between the served log-probs of the two label
  tokens and the reference's.
* ``filter_output_mismatches``: rows a cascade returned (a sample of up to
  ``MAX_EXTRA`` drawn from the seed, whichever model decided them, plus the
  oracle sample's returned rows) that the reference rejects (its verdict is
  true when the <true> label's logit exceeds the <false> one's).  A cascade
  may drop a true row (its recall target allows it) but may not return a
  false one.  A row whose reference margin is within the configuration's
  ``near_tie_nats`` of zero, but not exactly zero, is a near-tie and does
  not count: the program's margin may differ from the reference's by twice
  its log-prob gap.
* ``served_token_gap`` (logits): on a sample of the generated requests with
  the longest in it, the widest gap by which a served token's reference
  logit lies below the reference's best at that position.
* ``map_text_mismatches``: generated answers whose text in the operator's
  output differs from the tokens the engine served.

``compare(..., control=True)`` puts the reference, computed one precision
step lower (float8 e4m3 weights and activations), in the program's place and
holds it to the same limits: it has to fail one of them.  The benchmark's
own runs never call it."""
from __future__ import annotations

import re

import numpy as np

from bench import configs
from bench.weights import FALSE_ID, TRUE_ID

BOS, PAD, EOS = 257, 256, 258
SPECIALS = {258: "<eos>", 259: "<true>", 260: "<false>", 261: "<A>", 262: "<B>",
            263: "<sep>"}
SAMPLE_ROWS = 32          # scored rows compared per model and run
SAMPLE_GEN = 16           # generated requests compared per run (24 tokens each)
MAX_EXTRA = 64            # output rows checked beyond the sample
BLOCK = 4                 # rows per reference call
_TAG = re.compile(r"#(\d+)")


def encode(text: str) -> list[int]:
    return [BOS] + list(text.encode("utf-8"))


def decode(ids) -> str:
    """Byte-level text of generated ids: bytes decoded run by run, the
    label and control tokens by name, anything else dropped."""
    out, buf = [], []
    for t in ids:
        if t < 256:
            buf.append(t)
            continue
        if buf:
            out.append(bytes(buf).decode("utf-8", errors="replace"))
            buf = []
        if t in SPECIALS:
            out.append(SPECIALS[t])
    if buf:
        out.append(bytes(buf).decode("utf-8", errors="replace"))
    return "".join(out)


def block_inputs(seqs: list, max_seq: int, positions: list | None = None):
    """One block of BLOCK rows for the reference: tokens [BLOCK, max_seq]
    right-padded, and the positions read [BLOCK, P] (the last token of each
    row unless ``positions`` says otherwise)."""
    positions = positions or [[len(s) - 1] for s in seqs]
    p_max = max(len(p) for p in positions)
    toks = np.full((BLOCK, max_seq), PAD, np.int32)
    at = np.zeros((BLOCK, p_max), np.int32)
    for j, (s, p) in enumerate(zip(seqs, positions)):
        toks[j, :len(s)] = s
        at[j] = list(p) + [p[-1]] * (p_max - len(p))
    return toks, at


def _logits(ref, weights, cfg, seqs, positions, max_seq, *, control):
    """Reference logits [n, P, V] (float64) in blocks of BLOCK rows."""
    out = []
    p_max = max(len(p) for p in positions)
    for i in range(0, len(seqs), BLOCK):
        chunk = seqs[i:i + BLOCK]
        pos = [list(p) + [p[-1]] * (p_max - len(p)) for p in positions[i:i + BLOCK]]
        toks, at = block_inputs(chunk, max_seq, pos)
        lg = np.asarray(ref.logits_at(weights, cfg, toks, at, control=control), np.float64)
        out.extend(lg[:len(chunk)])
    return out


def _logsoftmax(x):
    m = x.max(-1, keepdims=True)
    return x - m - np.log(np.exp(x - m).sum(-1, keepdims=True))


def _sample(rng, n: int, k: int, longest: int) -> list[int]:
    rest = [i for i in range(n) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[int(j)] for j in pick)


def _label_logprobs(weights, rcfg, prompts, max_seq, *, control):
    """The log-probs of the two label tokens [n, 2] after each prompt, from
    the reference module that the role's configuration ``rcfg`` names."""
    seqs = [encode(p)[:max_seq] for p in prompts]
    lg = _logits(configs.reference(rcfg), weights, rcfg, seqs, [[len(s) - 1] for s in seqs],
                 max_seq, control=control)
    return _logsoftmax(np.stack([x[0] for x in lg]))[:, [TRUE_ID, FALSE_ID]]


def readings(cell, weights: dict, kept: dict, queries: list, *, seed: int,
             control: bool = False, info: dict | None = None) -> dict:
    """The numbers compared, for the program (or, with ``control``, for the
    lower-precision reference in its place).  ``info`` receives what the
    comparison saw beside them (the reference's verdicts on the rows)."""
    cfg, mix = cell.config, cell.mix
    info = {} if info is None else info
    max_seq = int(cfg["serving"]["max_seq"])
    rng = np.random.default_rng([abs(int(seed)), 7])
    out = {"queries_failed": float(sum(q.status != "done" for q in queries))}
    op = mix["operator"]
    if op == "filter_cascade":
        out.update(_filter_readings(cfg, weights, kept, queries, rng, max_seq,
                                    control=control, info=info))
    elif op == "map":
        out.update(_map_readings(cfg, weights, kept, queries, rng, max_seq,
                                 control=control))
    return out


def _filter_readings(cfg, weights, kept, queries, rng, max_seq, *, control, info):
    out = {}
    roles = {"oracle": cfg, "proxy": cfg["proxy"]}
    returned = {r["id"] for q in queries if q.status == "done"
                for r in (q.handle.records or [])}
    asked = {r["id"] for q in queries for r in q.query.records}
    for role in ("oracle", "proxy"):
        rows = kept["scored"][role]
        if not rows:
            out[f"{role}_logprob_gap"] = float("inf")
            continue
        longest = max(range(len(rows)), key=lambda i: len(rows[i][0]))
        pick = _sample(rng, len(rows), SAMPLE_ROWS, longest)
        prompts = [rows[i][0] for i in pick]
        extra = []
        if role == "oracle":
            # every row a filter returned is a candidate, whichever model
            # decided it: the proxy scored them all
            by_id = {}
            for p, _, _ in kept["scored"]["proxy"] + rows:
                by_id.setdefault(_row_id(p, asked), p)
            ret = sorted(i for i in returned if i in by_id)
            info["rows_returned"] = len(returned)
            if ret:
                take = rng.choice(len(ret), size=min(MAX_EXTRA, len(ret)), replace=False)
                extra = [by_id[ret[int(j)]] for j in sorted(take)]
        ref = _label_logprobs(weights[role], roles[role], prompts + extra, max_seq,
                              control=False)
        if control:
            low = _label_logprobs(weights[role], roles[role], prompts + extra, max_seq,
                                  control=True)
            served = low[:len(prompts)]
        else:
            served = np.asarray([[rows[i][1], rows[i][2]] for i in pick])
        out[f"{role}_logprob_gap"] = float(np.abs(served - ref[:len(prompts)]).max())
        if role != "oracle":
            continue
        # a returned row must be one the reference says true to; with the
        # control in the program's place, a row it says true to
        margin = ref[:, 0] - ref[:, 1]
        if control:
            member = low[:, 0] > low[:, 1]
        else:
            member = np.array([_row_id(p, asked) in returned for p in prompts + extra])
        tol = float(cfg["near_tie_nats"])
        wrong = member & ~(margin > 0) & ~((margin != 0) & (np.abs(margin) <= tol))
        out["filter_output_mismatches"] = float(wrong.sum())
        info.update(reference_true_share=float(np.mean(margin[:len(prompts)] > 0)),
                    reference_margin_sd=float(np.std(margin[:len(prompts)])),
                    returned_rows_compared=int(member.sum()))
        if control:     # calibration runs also read the selectivity the rows had
            every = kept["scored"]["proxy"]
            take = rng.choice(len(every), size=min(SAMPLE_ROWS, len(every)), replace=False)
            lp = _label_logprobs(weights[role], roles[role],
                                 [every[int(j)][0] for j in take], max_seq, control=False)
            info["uniform_true_share"] = float(np.mean(lp[:, 0] > lp[:, 1]))
            info["uniform_margin_sd"] = float(np.std(lp[:, 0] - lp[:, 1]))
    return out


def _map_readings(cfg, weights, kept, queries, rng, max_seq, *, control):
    out = {}
    gen = kept["generated"]
    answers = {}
    for q in queries:
        for r in (q.handle.records or []) if q.status == "done" else []:
            answers[r["id"]] = r.get("answer")
    ids = {r["id"] for q in queries for r in q.query.records}
    bad = 0
    for g in gen:
        seq = g["seq"]
        text = decode([t for t in seq["served"] if t != EOS]) if seq else None
        if seq is None or text != g["text"] or \
                answers.get(_row_id(g["prompt_text"], ids)) != g["text"]:
            bad += 1
    out["map_text_mismatches"] = float(bad)
    live = [g for g in gen if g["seq"] and g["seq"]["served"]]
    if not live:
        out["served_token_gap"] = float("inf")
        return out
    longest = max(range(len(live)),
                  key=lambda i: len(live[i]["seq"]["prompt"]) + len(live[i]["seq"]["served"]))
    pick = _sample(rng, len(live), SAMPLE_GEN, longest)
    seqs, positions, served = [], [], []
    for i in pick:
        s = live[i]["seq"]
        seqs.append(s["prompt"] + s["served"][:-1])
        positions.append([len(s["prompt"]) - 1 + k for k in range(len(s["served"]))])
        served.append(s["served"])
    ref_mod = configs.reference(cfg)
    ref = _logits(ref_mod, weights["oracle"], cfg, seqs, positions, max_seq, control=False)
    if control:
        low = _logits(ref_mod, weights["oracle"], cfg, seqs, positions, max_seq, control=True)
        served = [list(np.argmax(lo[:len(s)], -1)) for lo, s in zip(low, served)]
    gap = 0.0
    for lg, toks in zip(ref, served):
        for k, t in enumerate(toks):
            gap = max(gap, float(lg[k].max() - lg[k, int(t)]))
    out["served_token_gap"] = gap
    return out


def _row_id(prompt: str, ids) -> int | None:
    for m in _TAG.finditer(prompt):
        t = int(m.group(1))
        if t in ids:
            return t
    return None


def compare(cell, weights: dict, kept: dict, queries: list, *, seed: int,
            control: bool = False, info: dict | None = None) -> dict:
    """Each number compared beside its limit: ``{name: {value, limit}}``.
    With ``control`` the lower-precision reference stands in the program's
    place, and some number has to come out over its limit."""
    got = readings(cell, weights, kept, queries, seed=seed, control=control, info=info)
    return {k: {"value": v, "limit": cell.config["limits"][k]} for k, v in got.items()}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
