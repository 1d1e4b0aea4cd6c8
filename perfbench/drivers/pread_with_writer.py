"""Positional readers with writers beside them: clients ``0 .. readers-1``
are ``drivers/pread.py``'s readers over the shared files, the rest are
``drivers/write_files.py``'s writers, each writing files of its own back to
back through the window.  A DataNode that serves reads is as a rule being
written to as well; and only the writers' blocks give the device work in the
window, without which a traced run of a read cell has no operation to show.

Parameters: ``readers`` (the other clients write), then each driver's own.
"""

from __future__ import annotations

from drivers import pread, write_files


def _role(ctx):
    readers = int(ctx.params["readers"])
    if ctx.idx < readers:
        ctx.clients = readers       # the shared files are split over readers
        return pread
    return write_files


def prepare(ctx) -> None:
    _role(ctx).prepare(ctx)


def setup(ctx, client) -> list:
    return _role(ctx).setup(ctx, client)


def run(ctx, client, until: float) -> list:
    return _role(ctx).run(ctx, client, until)


def check(ctx, client) -> dict:
    return _role(ctx).check(ctx, client)
