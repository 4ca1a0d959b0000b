"""The tokenizer and the chat template, as the served path applies them (a
copy: ``utils/tokens.py`` ``ByteTokenizer``, ``model/chat_template.py``),
and the text the benchmark gives the rest of the model's vocabulary.

One token a byte going in; the benchmark's prompts are printable ASCII and
hold none of the tokenizer's special strings, so their ids are the bytes
themselves. Coming out, the model's vocabulary (152,064 ids for Qwen2.5)
is far wider than the byte tokenizer's 262, whose ``id_to_bytes`` has no
text above it — and the server streams text, so a token without text
would never reach the client. A deployment's tokenizer has text for every
id; here ``vocabulary_bytes`` stands in for it (``serving.register`` puts
it in the served tokenizer's place): an id under 128 is its ASCII
character, any other id ONE character of its own (code point 0x10000 +
id), so that a streamed text is its ids, one character a token, both ways.
"""

from __future__ import annotations

DEFAULT_SYSTEM = "You are a helpful assistant."
PLANE = 0x10000  # ids from 128 up live above the basic plane: no surrogates


def vocabulary_bytes(tid: int) -> bytes:
    """The UTF-8 text of one id: always one whole character."""
    return bytes([tid]) if tid < 128 else chr(PLANE + tid).encode("utf-8")


def vocabulary_text(ids) -> str:
    return "".join(chr(t) if t < 128 else chr(PLANE + t) for t in ids)


def ids_of_text(text: str) -> list[int]:
    """The ids a served text was streamed from (inverse of the above)."""
    return [ord(c) if ord(c) < 128 else ord(c) - PLANE for c in text]


def split_messages(messages: list[dict]) -> tuple[str, list[tuple[str, str]], str]:
    system, turns = "", []
    for m in messages:
        if m["role"] == "system":
            system = m["content"] if not system else f"{system}\n{m['content']}"
        else:
            turns.append((m["role"], m["content"]))
    user = turns.pop()[1]
    return system or DEFAULT_SYSTEM, turns, user


def render_chatml(system, history, user) -> str:
    def msg(role, content):
        return f"<|im_start|>{role}\n{content}<|im_end|>\n"

    return ("".join([msg("system", system)]
                    + [msg(r, c) for r, c in history] + [msg("user", user)])
            + "<|im_start|>assistant\n")


RENDERERS = {"qwen2": render_chatml}


def prompt_ids(messages: list[dict], family: str) -> list[int]:
    text = RENDERERS[family](*split_messages(messages))
    return list(text.encode("utf-8"))
