"""Seconds of ``Database.create_table`` in set-up, on the host clock:
the suffix-array build (``core/suffix_array.py`` ``_build_jit``, its
program loaded from the compile cache), the copy back, the store's
upload and the durable snapshot."""


def read(ctx):
    return ctx["phases"].get("create_s")
