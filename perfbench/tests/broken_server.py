"""The served path with a fault planted underneath, for
``test_correct.py``: starts ``engine serve`` like the real launcher, after
breaking one thing the way a wrong optimisation could.

    python broken_server.py <fault> engine serve ...

``altered-token``: the fourth token of every stream is replaced where the
server produces its chunks (the engine goes on from the true token).
``wrong-weights``: the model is initialised from another seed than the
one it was asked for.
"""

import sys


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    from fusioninfer_tpu import cli
    from fusioninfer_tpu.engine import server

    if fault == "altered-token":
        real = server.EngineServer._stream_chunks

        def altered(self, *args, **kwargs):
            n = 0
            for chunk in real(self, *args, **kwargs):
                if chunk and chunk.get("choices") and "token_id" in chunk["choices"][0]:
                    n += 1
                    if n == 4:
                        choice = chunk["choices"][0]
                        choice["token_id"] = (choice["token_id"] + 1) % 4096
                yield chunk

        server.EngineServer._stream_chunks = altered
    elif fault == "wrong-weights":
        at = argv.index("--seed") + 1
        argv[at] = str(int(argv[at]) + 1)
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return cli.main(argv)


if __name__ == "__main__":
    sys.exit(main())
