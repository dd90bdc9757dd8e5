"""Tokens delivered to the clients in the window over its length."""

import readers


def read(rec):
    return readers.tokens_in_window(rec) / rec["seconds"]
