"""Seconds of ``Database.freeze`` in set-up, on the host clock: the
FM-index build on the host (``api/fm.py`` ``FMIndex.build``) and its
persisted artifact."""


def read(ctx):
    return ctx["phases"].get("freeze_s")
