"""95th percentile of every gap between two consecutive output tokens of a
request, as the client saw them in the window (host clock). The cell runs
at saturation, where a tail swings with the smallest change, so it is read
beside the end-to-end metrics and not held to a bound."""


def read(r):
    return r.client.get("itl_p95_ms")
