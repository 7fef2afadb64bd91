"""Mean engine iterations of the traced fits (a count)."""


def read(ctx):
    if ctx.entry != "fit" or ctx.trace is None or not ctx.answers:
        return None
    return sum(a["iterations"] for a in ctx.answers) / len(ctx.answers)
