"""``flash_decode_paged`` over a kv8 pool: the attended lengths of each
traced decode call come from the harness's host counters (the kernel's
operands are the whole pool, whatever the context)."""
from bench import model as bm, readers, roofline


def cost(ctx: list, heads: int, head_dim: int, page: int,
         code_bytes: int = 1, scale_bytes: int = 4) -> tuple[float, float]:
    """(ops, bytes) of one layer's call over rows whose attended lengths
    are ``ctx``: the kv8 codes and per-(token, head) scales of K and V in
    whole pages, and the f32 query and output rows.  Ops are the two dot
    products, 4 * heads * head_dim per token."""
    toks = sum(-(-c // page) * page for c in ctx)
    kv = 2.0 * toks * heads * (head_dim * code_bytes + scale_bytes)
    qo = 2.0 * len(ctx) * heads * head_dim * 4
    return 4.0 * heads * head_dim * sum(ctx), kv + qo


def bound_s(ctx: dict, prog: str, events: list, pk: dict) -> float:
    m = bm.dims(ctx["cfg"])
    page = ctx["cfg"]["serving"]["page_size"]
    total = 0.0
    for call in readers.traced_calls(ctx, prog):
        o, b = cost(call[3], m["h"], m["hd"], page)
        total += m["L"] * roofline.bound_s(o, b, pk["bf16_flops"],
                                           pk["hbm_bytes_s"])[0]
    return total
