"""Share of the window's token slots the packer left as padding: the
growth of the program's counter ``lm.pad_tokens`` over that of
``lm.tokens`` + ``lm.pad_tokens``, in %. A program that counts neither
gives ``None``."""


def read(ctx):
    def grown(name):
        return ctx["after"]["counters"].get(name, 0) \
            - ctx["before"]["counters"].get(name, 0)
    slots = grown("lm.tokens") + grown("lm.pad_tokens")
    if slots <= 0:
        return None
    return 100.0 * grown("lm.pad_tokens") / slots
