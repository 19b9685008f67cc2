"""Device milliseconds per step of a loop: the device seconds of the loop's
own operation (a container: its body's events lie inside it) in the programs
matching spec["programs"], over the traced iterations times the steps one
iteration runs, which the program logs under spec["steps_key"] in its
phase-window records (median; every iteration of these cells runs the same
number). Of the containers whose label (`<name> <opcode> -> <result types>`:
the reduction keeps an operation's text only to 1,200 characters, and a
loop's result types alone are longer) matches spec["select"], the loop is the
one with most device time: a loop inside the loop (a scan over layers) takes
less. Nothing where the trace shows no such loop or the program logs no step
count."""

import re
import statistics


def read(ctx, spec):
    red, traced = ctx["reduction"], ctx["traced"]
    steps = [p[spec["steps_key"]] for p in ctx["window"]["phases"] if p.get(spec["steps_key"])]
    if not red or not traced or not traced.get("iterations") or not steps:
        return None
    loops = [row for name, row in red["ops"].items()
             if row["container"] and re.search(spec["programs"], name.split("/", 1)[0])
             and re.search(spec["select"], row["label"])]
    if not loops:
        return None
    seconds = max(row["seconds"] for row in loops)
    return 1000.0 * seconds / (traced["iterations"] * statistics.median(steps))
