"""The share of the attention kinds' device time that ran under any
``part/`` scope (part_ms.py): the guard that a refactor has not dropped a
part, as ``scoped_share`` is for the stages. None on a program without the
``part/`` scopes or where no operation of ``kinds`` ran."""
from perfbench.metrics import part_ms


def read(ctx, kinds):
    found = part_ms.part_map(ctx)
    parts = {part for _, part, _ in (found or {}).values() if part}
    if not parts:
        return None
    named = part_ms.seconds(ctx, found, kinds, parts)
    whole = part_ms.seconds(ctx, found, kinds)
    return 100.0 * named / whole if named is not None and whole else None
