"""What a window measured, and whether what it served was correct.

End-to-end readings come from the harness's own stamps on the host clock.
Correctness compares served tokens with the configuration's plain
reference: for a sample of requests, drawn from the seed and always
holding the one with the most served tokens, the reference reads every
served token's logit against its own best logit at that position.  The
widest and the mean of those gaps are the numbers compared.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness.drive import percentile

SAMPLE = 4          # requests compared per run (fixed: one compiled shape)


@dataclasses.dataclass
class Readings:
    attempted: int
    tokens: int
    window_s: float
    ttft_ms: list
    itl_ms: list
    queue_ms: list
    decode_tokens: int
    decode_steps: int
    slots: int

    @classmethod
    def collect(cls, driver, t0: float, t1: float, mix: dict) -> "Readings":
        tokens = 0
        ttft, itl, queue = [], [], []
        attempted = 0
        for s in driver.served.values():
            inside = [t for t in s.stamps if t0 < t <= t1]
            tokens += len(inside)
            if s.due <= t1 and not (s.done and s.stamps[-1] <= t0):
                attempted += 1          # owed or in flight in the window
            if t0 <= s.due < t1:
                first = s.stamps[0] if s.stamps else None
                ttft.append(((first if first is not None and first <= t1
                              else t1) - s.due) * 1e3)
                adm = s.admitted
                queue.append(((adm if adm is not None and adm <= t1
                               else t1) - s.due) * 1e3)
            itl += [(b - a) * 1e3 for a, b in zip(s.stamps, s.stamps[1:])
                    if a >= t0 and b <= t1]
        recs = driver.window_records()
        steps = [r for r in recs if r.decoded]
        return cls(attempted, tokens, t1 - t0, ttft, itl, queue,
                   sum(r.decoded for r in steps), len(steps), mix["slots"])

    def end_to_end(self) -> dict:
        out = {"output_tok_s": self.tokens / self.window_s}
        if self.itl_ms:
            out["itl_p50_ms"] = percentile(self.itl_ms, 50)
        return out


def sample(driver, seed: int) -> list[tuple[np.ndarray, list]]:
    """Up to ``SAMPLE`` served requests: the one with the most served
    tokens, and others drawn from the seed among those with two or more."""
    cands = sorted((s for s in driver.served.values() if len(s.tokens) >= 2),
                   key=lambda s: (-len(s.tokens), s.req.rid))
    if not cands:
        return []
    rest = cands[1:]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), min(SAMPLE - 1, len(rest)), replace=False)
    chosen = [cands[0]] + [rest[i] for i in sorted(pick)]
    return [(s.req.prompt, list(s.tokens)) for s in chosen]


def layout(sample, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, query) arrays of shape (SAMPLE, seq_len) for the reference:
    each row is the prompt then the served tokens but the last; the
    query at position ``plen - 1 + i`` is served token ``i``."""
    tokens = np.zeros((SAMPLE, seq_len), np.int32)
    query = np.full((SAMPLE, seq_len), -1, np.int32)
    for b, (prompt, served) in enumerate(sample):
        plen = len(prompt)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if len(seq) > seq_len:
            raise ValueError(f"sample row of {len(seq)} > {seq_len}")
        tokens[b, :len(seq)] = seq
        query[b, plen - 1:plen - 1 + len(served)] = served
    return tokens, query


def gap_numbers(gaps: np.ndarray, query: np.ndarray) -> dict:
    asked = gaps[query >= 0]
    if asked.size == 0:
        return {"max_gap": float("inf"), "mean_gap": float("inf"),
                "compared": 0}
    return {"max_gap": float(asked.max()), "mean_gap": float(asked.mean()),
            "compared": int(asked.size),
            "top1": float(np.mean(asked == 0))}


def compare(ref, cfg: dict, seed: int, sample, limits: dict, seq_len: int,
            query: np.ndarray | None = None) -> tuple[dict, bool, dict]:
    """Reference gaps of the served tokens, or of ``query`` (a control's
    first choices at the same positions).  Returns (checks, correct,
    info): ``checks`` maps each number compared to its value and limit,
    ``correct`` says whether every one keeps its limit."""
    tokens, served = layout(sample, seq_len)
    query = served if query is None else query
    nums = gap_numbers(ref.gaps(cfg, seed, tokens, query), query)
    checks = {k: {"value": nums[k], "limit": limits[k]}
              for k in ("max_gap", "mean_gap") if k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return checks, correct, {k: v for k, v in nums.items()
                             if k not in checks}
