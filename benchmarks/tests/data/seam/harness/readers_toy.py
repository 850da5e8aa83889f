"""A reader of another kind's records: denoising steps a block."""


def steps_per_block(ctx):
    finished = ctx.get("finished") or []
    blocks = sum(len(r["tokens"]) for r in finished) / ctx["fields"]["block"]
    if not blocks:
        return None
    return sum(len(r["scores"]) for r in finished) / blocks
