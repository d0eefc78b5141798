"""Plain reference of Federated Averaging over a parameter tree: each
client sends its dense update, the server applies the clients' mean."""

from __future__ import annotations

__all__ = ["CLIENT_STATE", "SERVER_STATE", "client", "server"]

CLIENT_STATE = SERVER_STATE = False


def client(delta, residual, traffic):
    return delta, residual


def server(mean_msg, residual, traffic):
    return mean_msg, residual
