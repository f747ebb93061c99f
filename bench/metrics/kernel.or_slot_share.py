"""Share of the outlier slots the CLAQ kernel's Outlier-Reservation
epilogue visits, in %: over every launch tile and K block of every
prepared weight, the slot window's width over all the group's slots,
weighted by the tile's elements.  The engine records it once, at
construction, in its recorder's registry; a program that keeps no such
gauge gives nothing."""


def read(ctx):
    registry = getattr(ctx.telemetry, "registry", None)
    gauge = (registry.get("kernel.or_slot_share")
             if registry is not None else None)
    return None if gauge is None else gauge.value
