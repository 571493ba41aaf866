"""Seconds from the start of set-up (data, index, requests, prepare, warm
and any compilation) until the window opens."""


def read(record):
    return record.setup_s
