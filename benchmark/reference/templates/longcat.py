"""Plain-text rounds, as the served path renders them for the ``longcat``
family (a copy: ``model/chat_template.py``, ``fmt="longcat"``). The
catalog's config has no template: assumed, in LongCat-Flash-Chat's shape —
no special strings, so every byte is a token of its own."""


def render(system: str, history: list[tuple[str, str]], user: str) -> str:
    out, n = [f"SYSTEM:{system}"], 0
    for role, content in list(history) + [("user", user)]:
        if role == "user":
            out.append(f" [Round {n}] USER:{content} ASSISTANT:")
            n += 1
        else:
            out.append(f"{content}</longcat_s>")
    return "".join(out)
