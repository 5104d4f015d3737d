"""Share of the KV pages the dispatched block tables span that the decode
attention read over the window: 100 x read / table of
``serving_kv_pages_total`` (``serving/engine.py`` ``_note_dispatch``). 100
where every step gathers whole tables; the live share of the tables where
``serving/paged_attention.py``'s kernel reads live pages in place. None for
a program without the counter."""
from lib.loopclock import delta

PAGES = "serving_kv_pages_total"


def read(ctx):
    pages_read = delta(ctx, PAGES, 'kind="read"')
    table = delta(ctx, PAGES, 'kind="table"')
    if pages_read is None or not table:
        return None
    return 100.0 * pages_read / table
