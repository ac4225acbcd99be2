from luminaai_tpu.serving.router import (
    CircuitBreaker,
    HttpTransport,
    Replica,
    Router,
)
from luminaai_tpu.serving.server import (
    ChatServer,
    ContinuousScheduler,
    build_server,
    serve,
)

__all__ = [
    "ChatServer",
    "CircuitBreaker",
    "ContinuousScheduler",
    "HttpTransport",
    "Replica",
    "Router",
    "build_server",
    "serve",
]
