"""Mean milliseconds of a request that the server's own timeline does not
see, over the window: the client's mean request->reply time minus
`server_request_ms`.  A request's bytes lie in socket buffers while the one
serving thread is blocked and no reader coroutine runs; the wire and the
client's own encode and decode are in it too."""

from benchmarks.layer_metrics import server_request_ms


def read(run):
    inside = server_request_ms.read(run)
    answered = [r for r in run["window"] if not r.error]
    if inside is None or not answered:
        return None
    seen = sum(r.t_reply - r.t_send for r in answered) / len(answered)
    return seen * 1e3 - inside
