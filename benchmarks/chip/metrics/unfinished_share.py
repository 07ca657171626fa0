"""unfinished_share (%, host clock): share of the judged (chat) requests
due in the window that the engine still holds, unfinished, at the close.
Above the knee the queue grows all through the window, so this is the
backlog that ``tokens_per_s`` leaves behind."""
from chipbench import derived


def read(ctx):
    due = derived.judged(ctx)
    if not due:
        return None
    late = sum(not x.done and not x.failed for x in due)
    return 100.0 * late / len(due)
