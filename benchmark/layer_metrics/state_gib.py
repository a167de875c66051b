"""GiB of placed training state one chip holds (parameters, optimizer,
sync and model state), from the shapes of the arrays the first step got
(the program's lifecycle record)."""
NAME, UNIT = "state_gib", "GiB"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    record = _lifecycle.lifecycle(ctx)
    if not record or not record["state_bytes"]:
        return None
    return sum(record["state_bytes"].values()) / _lifecycle.GIB
