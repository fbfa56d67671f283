"""Mean requests per projector batch, from the program's
``serve_occupancy`` counters."""


def read(ctx):
    occ = [c["fields"]["occupancy"] for c in ctx.spans
           if c.get("ev") == "ctr" and c["name"] == "serve_occupancy"]
    return sum(occ) / len(occ) if occ else None
