"""History core: op records, invoke/complete pairing, tensor encoding, WGL kernel."""

from .op import Op, INVOKE, OK, FAIL, INFO  # noqa: F401
from .encode import (  # noqa: F401
    NIL,
    F_READ,
    F_WRITE,
    F_CAS,
    EV_INVOKE,
    EV_RETURN,
    EV_PAD,
    Invocation,
    pair_history,
    encode_register_history,
    EncodedHistory,
)
