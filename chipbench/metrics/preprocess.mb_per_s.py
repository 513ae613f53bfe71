"""Raw corpus MiB over the preprocess CLI's wall time, in set-up
(host clock around ``preprocess_bert_pretrain.main``)."""

UNIT = "MB/s"
BETTER = "higher"
LAYER = "offline preprocess"
MOVES = "setup_s"


def read(record):
    if not record.get("preprocess_s"):
        return None
    return record["corpus_bytes"] / 2 ** 20 / record["preprocess_s"]
