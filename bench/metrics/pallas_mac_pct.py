"""Share of the conv and fc MACs (depthwise included) that the plan gives
to a Pallas kernel (``matmul:pallas`` or ``conv:pallas``), counted from
``CompiledChain.dispatch`` and the chain's GCONV shapes. Moves
``images_per_s``."""
from bench import work


def read(ctx):
    by_tag = work.planned_macs(ctx.chain, ctx.engine.dispatch)
    total = sum(by_tag.values())
    pallas = sum(m for tag, m in by_tag.items() if tag.endswith(":pallas"))
    return 100.0 * pallas / total if total else None
