"""Iterator factory: ordered ``iter = type ...`` config -> iterator chain.

Parity with ``/root/reference/src/io/data.cpp:27-94``: the first
``iter=`` names the base source; later ``iter=`` entries stack adapters
(``threadbuffer``, ``membuffer``); parameters apply to every iterator in
the chain (the reference calls SetParam down the chain).

Sources: mnist, tokens (batch-level); csv / img / imgrec / imgbin (instance
level, auto-wrapped in a BatchAdapter like the reference's
CreateBatchIter). Adapters: augment, batch, threadbuffer, membuffer,
attachtxt.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from .data import DataBatch, DataInst, IIterator
from .iter_batch import BatchAdapter, PrefetchIterator
from .iter_csv import CSVIterator
from .iter_libsvm import LibSVMIterator
from .iter_mnist import MNISTIterator
from .iter_mem import MemBufferIterator
from .iter_img import ImageIterator
from .iter_imgrec import ImageRecordIterator
from .iter_augment import AugmentAdapter
from .iter_attach import AttachTxtIterator
from .iter_imgbin import ImageBinIterator
from .iter_tokens import TokenIterator



def create_iterator(cfg: Sequence[Tuple[str, str]],
                    global_cfg: Sequence[Tuple[str, str]] = ()) -> IIterator:
    """Build an iterator chain from an ordered iterator block.

    cfg starts with one or more ('iter', type) entries interleaved with
    their parameters, exactly as split_sections emits them. global_cfg
    (batch_size, input_shape...) is applied to the whole chain first,
    mirroring the CLI driver passing global params into iterators
    (cxxnet_main.cpp:266-315).
    """
    it: IIterator = None
    pending: List[Tuple[str, str]] = list(global_cfg)
    is_instance_level = False

    def apply_pending(target: IIterator):
        for name, val in pending:
            target.set_param(name, val)

    for name, val in cfg:
        if name == "iter":
            if val == "mnist":
                assert it is None, "mnist must be the base iterator"
                it = MNISTIterator()
                is_instance_level = False
            elif val == "csv":
                assert it is None, "csv must be the base iterator"
                it = CSVIterator()
                is_instance_level = True
            elif val == "tokens":
                assert it is None, "tokens must be the base iterator"
                it = TokenIterator()
                is_instance_level = False
            elif val == "libsvm":
                assert it is None, "libsvm must be the base iterator"
                it = LibSVMIterator()
                is_instance_level = True
            elif val == "img":
                assert it is None, "img must be the base iterator"
                # image sources get the augmenter inline: crop/mirror/
                # mean/scale params live in the same block, as in the
                # reference's image iterators
                it = AugmentAdapter(ImageIterator())
                is_instance_level = True
            elif val == "imgrec":
                assert it is None, "imgrec must be the base iterator"
                it = AugmentAdapter(ImageRecordIterator())
                is_instance_level = True
            elif val in ("imgbin", "imgbinx", "imgbinold", "imginst"):
                # one iterator serves all legacy imgbin variants (their
                # differences were threading strategies; see
                # iter_imgbin.py)
                assert it is None, "imgbin must be the base iterator"
                it = AugmentAdapter(ImageBinIterator())
                is_instance_level = True
            elif val == "augment":
                assert it is not None and is_instance_level, \
                    "augment stacks on an instance iterator"
                # image sources already carry an inline augmenter; a
                # second one would apply scale/mean twice (params forward
                # through to the base), so reuse it
                if not isinstance(it, AugmentAdapter):
                    it = AugmentAdapter(it)
            elif val == "batch":
                assert it is not None and is_instance_level
                it = BatchAdapter(it)
                is_instance_level = False
            elif val == "threadbuffer":
                assert it is not None, "threadbuffer stacks on an iterator"
                if is_instance_level:
                    it = BatchAdapter(it)
                    is_instance_level = False
                it = PrefetchIterator(it)
            elif val == "membuffer":
                assert it is not None, "membuffer stacks on an iterator"
                if is_instance_level:
                    it = BatchAdapter(it)
                    is_instance_level = False
                it = MemBufferIterator(it)
            elif val == "attachtxt":
                assert it is not None, "attachtxt stacks on an iterator"
                if is_instance_level:
                    it = BatchAdapter(it)
                    is_instance_level = False
                it = AttachTxtIterator(it)
            else:
                raise ValueError("unknown iterator type %r" % val)
            apply_pending(it)
        else:
            if it is None:
                pending.append((name, val))
            else:
                it.set_param(name, val)
    if it is None:
        raise ValueError("no iterator configured")
    if is_instance_level:
        it = BatchAdapter(it)
        apply_pending(it)
        for name, val in cfg:
            if name != "iter":
                it.set_param(name, val)
    return it


__all__ = ["DataBatch", "DataInst", "IIterator", "create_iterator",
           "BatchAdapter", "PrefetchIterator", "MNISTIterator",
           "CSVIterator"]
