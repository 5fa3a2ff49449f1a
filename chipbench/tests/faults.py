"""Faults planted in the timed path, to show that the check catches each
one the cells can have: at test size on the CPU
(``test_chipbench_serve.py``) and at a cell's own size on the chip
(``fault_run.py``).  The exchange between chips left out is planted in
``ring_run.py``."""


def token_altered(vocab: int):
    """An executor hook: every third decode step, each row's new token is
    moved to the next id where it is produced, and the altered token is
    what the next step reads."""

    def plant(ex):
        decode = ex.decode
        calls = [0]

        def altered(states):
            decode(states)
            calls[0] += 1
            if calls[0] % 3 == 0:
                for st in states:
                    st.tokens[-1] = (st.tokens[-1] + 1) % vocab
                    ex.last_tok[st.slot] = st.tokens[-1]

        ex.decode = altered

    return plant


def graft_unchanged():
    """(module, name, replacement): a graft that returns the resident
    cache as it was, so an admitted row decodes over another's keys and
    values."""
    import repro.launch.serve as serve
    return serve, "_graft_to_device", \
        lambda big, small, slots, rows, sharding: big
