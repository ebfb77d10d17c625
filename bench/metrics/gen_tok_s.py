"""``gen_tok_s``: Output tokens emitted in the window per second of it."""
from harness.readers import gen_tok_s as read  # noqa: F401
