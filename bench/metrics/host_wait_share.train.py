"""Share of the training window's epoch time that the step loop spent
waiting on the host feed (sampling, layout, gather): the sum of
``run_epoch``'s ``host_wait_s`` over the sum of its ``epoch_time_s``."""


def read(ctx):
    rec = ctx["record"]
    if not rec.get("epoch_time_s"):
        return None
    return 100.0 * rec["host_wait_s"] / rec["epoch_time_s"]
