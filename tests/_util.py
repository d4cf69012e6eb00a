"""Helpers the tests share (imported as ``_util``: pytest puts this
directory on ``sys.path``)."""

import socket


def free_port() -> int:
    """An OS-assigned localhost TCP port (the wire-protocol tests bind
    throwaway ZMQ pairs)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
