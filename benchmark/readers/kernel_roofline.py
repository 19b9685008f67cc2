"""A kernel's share of its roofline: the least time the chip could take for
the calls the trace shows (per call the larger of operations over peak FLOP/s
and bytes over peak bytes/s, benchmark/flops.py) over the summed device time
of those calls, in every program that makes them.

The kernels carry no name in the trace, so a metric's file selects them (see
`trace.op_rows`) and this reader takes each call's shapes from the
operation's own HLO text: spec["function"] is "flash" (operands
bf16[batch*heads, positions, head_dim]; forward returns (o, lse), dkv returns
(dk, dv), dq returns dq) or "logprob_head" (x [rows, d], W [d, vocab] or
[vocab, d]; forward returns three [rows, 1] columns, dw a vocab-sized
result, dx the rest). Which layers are windowed cannot be read from a call,
so a flash call's floor is the mean over the configuration's layer kinds.
On several planes a row's seconds are the mean over the chips and its calls
the sum, so the calls are divided by the planes too. Returns nothing where
the trace shows no such call.
"""

import re


def flash_shape(text):
    m = re.match(r"^%\S+ = (\(?)bf16\[(\d+),(\d+),(\d+)\](?:, (\w+)\[)?", text)
    if not m:
        return None
    kind = "bwd_dq" if not m.group(1) else ("fwd" if m.group(5) == "f32" else "bwd_dkv")
    return kind, dict(b=1, t=int(m.group(3)), n_head=int(m.group(2)), head_dim=int(m.group(4)))


def head_shape(text):
    result = text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    shapes = [(int(a), int(b)) for a, b in re.findall(r"\w+\[(\d+),(\d+)\]", text)]
    wide = [s for s in shapes if min(s) > 1]
    if not wide:
        return None
    v = max(max(s) for s in wide)
    d = next(min(s) for s in wide if v in s)
    n = next((s[0] if s[1] == d else s[1] for s in wide if d in s and v not in s), None)
    if n is None:
        return None
    kind = "fwd" if result.startswith("(f32[") else ("bwd_dw" if str(v) in result else "bwd_dx")
    return kind, dict(n=n, d=d, v=v)


def read(ctx, spec):
    red, peaks, f = ctx["reduction"], ctx["peaks"], ctx["flops"]
    if not red or not peaks:
        return None
    least = spent = 0.0
    for row in ctx["trace"].op_rows(red, spec):
        if spec["function"] == "flash":
            parsed = flash_shape(row["text"])
            if parsed:
                kind, shape = parsed
                floors = [f.least_seconds(*f.flash_call(kind, window=w, **shape), peaks)[0]
                          for w in f.layer_windows(ctx["arch"])]
                floor = sum(floors) / len(floors)
        else:
            parsed = head_shape(row["text"])
            if parsed:
                floor = f.least_seconds(*f.logprob_head_call(parsed[0], **parsed[1]), peaks)[0]
        if parsed:
            least += row["calls"] / red["n_devices"] * floor
            spent += row["seconds"]
    return 100.0 * least / spent if spent else None
