"""Whether what the timed path served is right, against the plain
reference (``bench/reference.py``).

Numbers compared, each against its limit (``bench/limits/<config>.json``):

* ``failed``: requests sent in the window that did not end ``completed``
  (limit 0);
* ``nfe_off``: completed requests whose reported refine steps differ from
  ``ceil(cold_nfe * (1 - t0))`` or whose t0 is not the configured one
  (limit 0);
* ``draft_gap``: over a sample of served rows, the widest gap by which a
  draft token's Gumbel score (the reference LSTM's logits, teacher-forced
  on the row's draft, plus the row's own noise for that position) lies
  below the best score there. The draft samples by Gumbel-max with noise
  that the reference redraws exactly, so a sound draft loses to the
  reference's choice only at near-ties, by rounding;
* ``clear_mismatch``: over the same rows, the share of served tokens that
  differ from the reference chain (run from the row's own draft with the
  row's own per-step noise) at positions the reference decided clearly:
  at every step the best Gumbel score beat the second by at least the
  configuration's ``margin``. Rounding in the served path flips only
  near-ties, so these positions are where a sound program agrees and a
  less precise or broken one does not.

The sample is drawn from the seed among the completed requests' rows and
always holds a row of the longest request.

``control`` puts the cell's control in the program's place, one or more
parts joined by ``+``: a mode (``"bf16"``) picks the draft tokens by the
reference LSTM in that mode; ``"reference:<mode>"`` serves the refine
chain from the reference in that mode; ``"program:<dtype>"`` is the
program itself switched to that dtype (set up by the harness).
"""

from __future__ import annotations

from collections import defaultdict

import jax
import numpy as np

from bench import reference


def sample_rows(run, seed: int, count: int):
    """(request index, sample) pairs: a row of the longest completed
    request, then rows drawn from the seed."""
    done = sorted(i for i, r in run.requests.items()
                  if r["status"] == "completed")
    if not done:
        return []
    rows = [(i, s) for i in done for s in range(run.requests[i]["samples"])]
    longest = max(done, key=lambda i: (run.requests[i]["seq_len"], -i))
    rng = np.random.default_rng([int(seed) % 2 ** 63, 7])
    rest = [rows[j] for j in rng.permutation(len(rows))
            if rows[j] != (longest, 0)]
    return [(longest, 0)] + rest[:max(0, count - 1)]


def _batches(items, size):
    for i in range(0, len(items), size):
        chunk = items[i:i + size]
        yield chunk + [chunk[0]] * (size - len(chunk))


def compare(run, drafts: dict, w: dict, wl: dict, spec, seed: int, *,
            control: str = "") -> dict:
    """The raw readings: ``draft_gap``, and for every compared token
    whether it differs from the reference (``mismatch``) and the
    reference's smallest decision margin there (``margin``)."""
    cfg, lim = spec.config, spec.limits
    m, s, dr = cfg["model"], cfg["serving"], cfg["draft"]
    ref = lim["reference"]
    reqs = run.requests
    by_bucket = defaultdict(list)
    for i, smp in sample_rows(run, seed, lim["sample_rows"]):
        by_bucket[reqs[i]["bucket_len"]].append((i, smp))

    parts = control.split("+") if control else []
    draft_mode = next((c for c in parts if c in ("bf16", "int8")), "")
    chain_mode = next((c.split(":", 1)[1] for c in parts
                       if c.startswith("reference:")), "")
    gap, missing = 0.0, 0
    mismatch, margin = [], []
    for blen, items in sorted(by_bucket.items()):
        for batch in _batches(items, lim["ref_batch"]):
            dkeys, fkeys = reference.row_keys(
                [reqs[i]["seed"] for i, _ in batch], [k for _, k in batch])
            kd = np.asarray(jax.random.key_data(dkeys))
            x = []
            for b in range(len(batch)):
                row = drafts.get(tuple(kd[b].tolist()))
                if row is None or row.shape[0] != blen:
                    missing += 1
                    row = np.zeros((blen,), np.int32)
                x.append(row)
            x = np.stack(x)
            gaps = reference.draft_gaps(wl, dr["num_layers"], dr["bos"], x,
                                        dkeys, ref["draft"],
                                        control=draft_mode)
            gap = max(gap, float(gaps.max()))
            want, clear = reference.refine_chain(
                w, m, x, fkeys, s["cold_nfe"], s["t0"], ref["refine"])
            want, clear = np.asarray(want), np.asarray(clear)
            if chain_mode:
                got = np.asarray(reference.refine_chain(
                    w, m, x, fkeys, s["cold_nfe"], s["t0"], chain_mode)[0])
            seen = set()
            for b, (i, smp) in enumerate(batch):
                if (i, smp) in seen:
                    continue
                seen.add((i, smp))
                n = reqs[i]["seq_len"]
                served = got[b, :n] if chain_mode else reqs[i]["tokens"][smp]
                mismatch.append(served != want[b, :n])
                margin.append(clear[b, :n])
    return {"draft_gap": gap, "missing": missing,
            "mismatch": np.concatenate(mismatch) if mismatch else
            np.ones((1,), bool),
            "margin": np.concatenate(margin) if margin else
            np.full((1,), np.inf)}


def check(run, drafts: dict, w: dict, wl: dict, spec, seed: int, *,
          control: str = "", log=None) -> dict:
    """``{name: (value, limit)}``."""
    s, lim = spec.config["serving"], spec.limits
    want_nfe = reference.warm_steps(s["cold_nfe"], s["t0"])
    reqs = run.requests.values()
    failed = sum(r["status"] != "completed" for r in reqs)
    nfe_off = sum(r["status"] == "completed"
                  and (r["nfe"] != want_nfe or r["t0"] != s["t0"])
                  for r in reqs)
    c = compare(run, drafts, w, wl, spec, seed, control=control)
    clear = c["mismatch"] & (c["margin"] >= lim["margin"])
    share = 1.0 if c["missing"] else float(clear.mean())
    if log is not None:
        log(f"info compared_tokens {c['mismatch'].size} mismatch_all "
            f"{float(c['mismatch'].mean())!r} missing_drafts {c['missing']}")
    limits = lim["limits"]
    return {
        "failed": (failed, 0),
        "nfe_off": (nfe_off, 0),
        "draft_gap": (c["draft_gap"], limits["draft_gap"]),
        "clear_mismatch": (share, limits["clear_mismatch"]),
    }
