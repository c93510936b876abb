from .trace import span  # noqa: F401
