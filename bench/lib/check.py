"""The comparison that decides `correct`.

Served tokens: after the window, a sample drawn from the seed of the
requests the window finished, the longest among them, until it holds
`sample_tokens` served tokens; on a fleet, topped up in the same seeded
order until every slice that finished a request has one in it. The
float32 reference runs once over each
prompt with its served tokens (teacher forcing) and the number compared is
the widest gap by which a served token's reference logit lies below the
reference's best logit at that position. Greedy decoding serves the
argmax, so a correct program reads a gap of rounding size; a wrong token
reads the spread of the logits.

DPU outputs: a sample drawn from the seed of the requests whose features
the DPU service handed to admission, compared with the numpy front end
(largest absolute difference of the normalized log-mel features).

The control (`control=True`, calibration only) puts the reference in the
program's place at the precision below the stated one: fp8 (e4m3) matmul
operands for the bfloat16 model, reading at each position the gap of the
token the fp8 reference ranks first, and bfloat16 matmul operands for the
float32 front end.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.reference import audio_frontend


def token_sample(served, seed: int, want_tokens: int,
                 slice_of: Optional[Dict[int, str]] = None) -> List:
    """`slice_of` ({rid: slice}) names the slice that served each request
    on a fleet; None on one engine."""
    done = [s for s in served if s.status == "completed" and s.tokens is not None
            and len(s.tokens)]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.plan.prompt) + len(s.tokens),
                                       -s.plan.index))
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= want_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    if slice_of:
        have = {slice_of.get(s.plan.index) for s in out}
        for i in order:
            sl = slice_of.get(rest[i].plan.index)
            if sl is not None and sl not in have:
                out.append(rest[i])
                have.add(sl)
    return out


def retiring_slice(events) -> Dict[int, str]:
    """{rid: slice} from the first RETIRE event of each request."""
    out: Dict[int, str] = {}
    for e in events:
        if e.kind == "retire" and e.rid is not None:
            out.setdefault(e.rid, e.sid)
    return out


def token_gaps(ref, params, sample, spec: Dict, control: bool = False):
    """(widest gap of the served tokens, widest gap of the control's first
    choices or None, number of tokens compared)."""
    seqs, rows, served = [], [], []
    for s in sample:
        p, t = s.plan.prompt, s.tokens
        seqs.append(np.concatenate([p, t[:-1]]).astype(np.int32))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(t)))
        served.append(t)
    if not seqs:
        return None, None, 0
    lg = ref.logits(params, seqs, rows, spec)
    gap = max(float(np.max(l.max(-1) - l[np.arange(len(t)), t]))
              for l, t in zip(lg, served))
    cgap = None
    if control:
        lq = ref.logits(params, seqs, rows, spec, quant=True)
        cgap = max(float(np.max(l.max(-1) - l[np.arange(len(l)), q.argmax(-1)]))
                   for l, q in zip(lg, lq))
    return gap, cgap, sum(len(t) for t in served)


def dpu_errors(served, seed: int, n: int, control: bool = False):
    """(largest |DPU - numpy| over a seeded sample, the bf16 control's
    largest difference or None, clips compared)."""
    have = [s for s in served if s.features is not None]
    if not have:
        return None, None, 0
    idx = np.random.default_rng([seed, 4]).permutation(len(have))[:n]
    err, cerr = 0.0, 0.0 if control else None
    for i in idx:
        s = have[i]
        ref = audio_frontend.features(s.plan.audio)
        err = max(err, float(np.max(np.abs(np.asarray(s.features) - ref))))
        if control:
            c = audio_frontend.features(s.plan.audio, bf16=True)
            cerr = max(cerr, float(np.max(np.abs(c - ref))))
    return err, cerr, len(idx)
