"""Share of the held experts that decode steps touched, in percent:
``DecodeWorker`` counters ``moe_touched`` (over steps and expert layers,
the held experts that got at least one token of an active slot) over
``moe_held`` (steps × expert layers × experts held), over the window. A
program without those counters reads nothing. Moves ``output_tok_s``:
the step reads the weights of every held expert, touched or not, so the
share says how much of that read a dispatch that skips untouched experts
would save."""


def read(ctx):
    a, b = ctx.counters0["decode"], ctx.counters1["decode"]
    if "moe_touched" not in b or "moe_held" not in b:
        return None
    held = b["moe_held"] - a["moe_held"]
    if held == 0:
        return None
    return 100.0 * (b["moe_touched"] - a["moe_touched"]) / held
