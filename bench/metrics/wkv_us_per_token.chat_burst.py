"""Device time of the RWKV recurrence per real token and layer in the
profiled slice, in microseconds.

The recurrence's operations are those whose result carries the recurrent
state, an f32 array whose trailing shape is ``[heads, head_size,
head_size]``, and nothing else but the per-head streams (trailing ``[heads,
head_size]``) and scalars: the prefill's time-step loops and their steps,
the decode step's state update, and the copies of state rows between the
pool and the layers.  A layer loop also carries activations, and the pool's
row loops carry index arrays, so neither matches as a whole.  An operation
named ``%wkv...`` (a recurrence kernel) matches too.  Nested operations
count once: the time is the union of their intervals.  The token steps are
counted from the harness's stamps in the slice, as ``mfu_pct`` counts
served tokens: a first token brings its prompt's real tokens, every later
token one, each times the layers.
"""
import re

import devtrace

KERNEL = "%wkv"
ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def result_arrays(name):
    """(dtype, dims) of each array in an HLO instruction's result type."""
    _, _, rest = name.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        res = rest[:end + 1]
    else:
        res = rest.split(" ", 1)[0]
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in ARRAY.findall(res)]


def is_recurrence(name, heads, size):
    if name.startswith(KERNEL):
        return True
    state, stream = (heads, size, size), (heads, size)
    arrays = result_arrays(name)
    return (any(t == "f32" and d[-3:] == state for t, d in arrays)
            and all(not d or (t == "f32" and (d[-3:] == state or d[-2:] == stream))
                    for t, d in arrays))


def recurrence_seconds(events, win, heads, size):
    """Union of the matched operations' intervals in ``win``, averaged over
    the devices."""
    per = []
    for ops in events["devices"].values():
        hits = [(o[1], o[2]) for o in ops if is_recurrence(o[0], heads, size)]
        per.append(sum(b - a for a, b in devtrace.clip(devtrace.union(hits), *win)))
    return sum(per) / len(per) if per else 0.0


def read(ctx):
    cfg, prof, ev, win = ctx["cfg"], ctx["prof"], ctx["events"], ctx["traced"]
    if prof.get("start") is None or prof.get("stop") is None or ev is None \
            or win is None or cfg["block"] != "rwkv":
        return None
    size = cfg["head_size"]
    seconds = recurrence_seconds(ev, win, cfg["hidden_size"] // size, size)
    tokens = 0
    for f in ctx["window"].flights:
        for j, t in enumerate(f.stamps):
            if prof["start"] <= t <= prof["stop"]:
                tokens += len(f.prompt) if j == 0 else 1
    steps = tokens * cfg["num_hidden_layers"]
    if seconds <= 0.0 or steps == 0:
        return None
    return 1e6 * seconds / steps
