"""Blocking device-to-host reads of one ``quantize()`` call: the program's
counter ``QuantReport.host_reads`` of the window's last call (every call
of a window quantizes the same pair)."""


def read(ctx):
    return ctx["work"].get("host_reads")
