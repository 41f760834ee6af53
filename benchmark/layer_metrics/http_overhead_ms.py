"""What the HTTP front end adds: the median, over requests, of the
latency the client saw (from sending, not from the due time) less the
batcher's enqueue-to-answer time for the same request (joined on the
``X-Request-Id`` the generator sends)."""
from benchmark import harness


def read(run):
    serve = run.get('serve') or {}
    inner = serve.get('batcher_ms')
    if not inner:
        return None
    extra = [r['done'] - r['sent'] - inner[r['id']] / 1e3
             for r in serve['requests']
             if r['ok'] and r['id'] in inner]
    return 1e3 * harness.median(extra) if extra else None
