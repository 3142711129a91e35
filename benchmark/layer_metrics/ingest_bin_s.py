"""Seconds inside the program's ingest calls (`create_from_sample`,
`push_rows`, `finish_load`): bin finding on the sample and the native
value-to-bin pass over every row. Waiting for the generator is not in it."""


def read(ctx):
    return ctx["walls"].get("ingest_bin_s")
