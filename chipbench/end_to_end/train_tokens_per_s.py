"""Tokens of every step finished in the window (global batch x sequence
length) over the whole window, which the last fetched loss closes."""


def read(obs):
    return obs["tokens_in_window"] / obs["window_s"]
