"""Share of the logical 8x8 blocks of activation-side operands that the
block-skip route did not store, over the window's compiled batches (the
program's activation telemetry).  Nothing to read where no kernel takes
that route."""


def read(run):
    logical = sum(a["logical_blocks"] for a in run.activation)
    if not logical:
        return None
    stored = sum(a["stored_blocks"] for a in run.activation)
    return 100.0 * (1.0 - stored / logical)
