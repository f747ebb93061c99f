from . import context, hlo_analysis, sharding  # noqa: F401
