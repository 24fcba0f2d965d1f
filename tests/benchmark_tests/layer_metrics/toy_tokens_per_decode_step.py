"""A reader that only the tests' rehearsal of an appended per-layer entry
lists (test_bench_manifest.py): it stands for the reader a later PR
brings under a path of the manifest's. Server: the tokens that reached a
client in the window (a prefill's first token among them) over the decode
steps made in it; nothing where no step was made."""


def read(facts):
    steps = facts.get('decode_steps')
    if not steps or 'tokens' not in facts:
        return None
    return float(facts['tokens']) / steps
