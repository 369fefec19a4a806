"""95th percentile of admission minus due time over the requests due in
the window (a request not admitted by the window's end counts with its
wait so far)."""


def read(run):
    return run.e2e["queue_wait_p95_s"]
