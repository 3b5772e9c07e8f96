"""Share of the copy traffic that was thrown away: bytes copied by the
migration engine (``MigrationStats.bytes_copied``) beyond the bytes of the
blocks that committed, over the window, in %."""


def read(ctx):
    copied = ctx.facts["bytes_copied"]
    if not copied:
        return None
    return 100.0 * (copied - ctx.facts["useful_bytes"]) / copied
