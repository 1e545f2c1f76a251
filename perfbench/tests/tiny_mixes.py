"""Tiny versions of the mixes added after ``tiny.py``: importing this
module adds them to ``tiny.MIXES``, where ``tiny.make`` looks up the mix of
every cell of the benchmark."""
import tiny

MIXES = {
    "prefill-long": {"clients": 4, "slots": 4, "page_size": 16,
                     "warm_s": 0.5, "block": 16,
                     "prompt_tokens": {"median": 48, "min": 32, "max": 64},
                     "output_tokens": {"median": 6, "min": 4, "max": 8}},
}
for _name, _mix in MIXES.items():
    tiny.MIXES.setdefault(_name, _mix)
