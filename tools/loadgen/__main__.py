"""``python -m tools.loadgen convert <src> <dst>`` — the trace
converter: public Azure/Mooncake trace rows → the replayable
``load_trace`` JSONL shape (tools/loadgen/convert.py).
"""
import sys


def main() -> None:
    if len(sys.argv) < 2 or sys.argv[1] != "convert":
        sys.exit("usage: python -m tools.loadgen convert <src> <dst> "
                 "[--format auto|azure|mooncake] [--limit N]")
    from tools.loadgen.convert import main as convert_main
    return convert_main(sys.argv[2:])


if __name__ == "__main__":
    main()
