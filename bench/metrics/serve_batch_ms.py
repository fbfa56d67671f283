"""Mean duration of the projector's ``serve_batch`` spans: stacking,
copying and projecting one batch on the host's clock."""


def read(ctx):
    d = [s["dur"] for s in ctx.spans if s.get("ev") == "span" and s["name"] == "serve_batch"]
    return 1e3 * sum(d) / len(d) if d else None
