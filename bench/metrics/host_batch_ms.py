"""Mean host time per window step in ``make_batch`` + ``shard_batch``
(the harness's spans around the trainer's calls)."""


def read(ctx):
    per = [s.get("batch_build", 0.0) + s.get("transfer", 0.0)
           for s in ctx.spans]
    return 1e3 * sum(per) / len(per) if per else None
