"""Output tokens received by clients in the window over its length.  Below
the knee this is what the traffic offers; it is the judged number of a
saturated cell."""


def read(obs):
    return obs["tokens_in_window"] / obs["window_s"]
